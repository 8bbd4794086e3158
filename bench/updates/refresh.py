"""Update kind ``refresh``, as ``bench/kinds.py`` asks of an update module:
fresh weights for every update, then a full ``refresh()`` of the served
table; the features stay as set-up made them. Update ``i`` of the window
serves the weights ``params_fn(key, i + 2)`` (0 and 1 are set-up's)."""
import numpy as np


def apply(srv, params_fn, key, i: int) -> None:
    srv.update_params(params_fn(key, np.int32(i + 2)))
    srv.refresh()


def served(x, params_fn, key, u: int) -> tuple:
    return x, params_fn(key, np.int32(u + 2))

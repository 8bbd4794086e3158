"""The kinds a cell's files name, each a module of its own, found by its
file name: a new kind is a new file, and a kind with no file is refused.

  ``models/<kind>.py``    the configuration's ``model.kind``
  ``updates/<kind>.py``   the traffic mix's ``update.kind``
  ``metrics/<name>.py``   a metric of ``BENCHMARK.json`` (its ``read``)

A model module holds everything the harness, the faults and the readers
know of the model:

  ``NUMERICS``        the configuration ``numerics`` its reference can judge
  ``PRECISIONS``      the reference's precisions, among them ``"float32"``
                      (printed beside the judged one) and ``"bfloat16"``
                      (the control, ``faults.broken("control", kind)``)
  ``JUDGED``          the precision ``correct`` is decided against
  ``graph_weights(g)``            ``(edge_weight [E], self_loop [N])``
                                  float32 stored in the plan's ``Graph``
  ``params_fn(model)``            ``fn(key, i)``: the parameters of update
                                  ``i``, drawn from the key
  ``program_config(model, numerics)``  the config ``GNNServer`` runs
  ``forward(x, nbr, wts, params_list, precision)``  the plain reference:
                                  the served table ``[N, out]`` float32
                                  for each parameter set
  ``widths(model)``               the layer widths, input to output
  ``work(model, n_nodes, rows)``  ``(model FLOPs of one update, per device
                                  and layer {"flops", "bytes"})``

where ``model`` is the configuration's ``model`` section. An update
module defines one update of the window:

  ``apply(srv, params_fn, key, i)``  update ``i`` of the window on the
                                     server; ``i = -1`` is set-up's warm one
  ``served(x, params_fn, key, u)``   ``(features, params)``: what the
                                     reference is given to recompute the
                                     table update ``u`` served
"""
from __future__ import annotations

import importlib.util
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
WHAT = {"models": "model kind", "updates": "update kind",
        "metrics": "metric"}
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
_LOADED: dict = {}      # path -> module, so that every caller shares one


class Refused(ValueError):
    """A kind that has no module, or that its module cannot run."""


def known(group: str, root: str = HERE) -> list:
    """The names of the modules in ``<root>/<group>``, sorted."""
    return sorted(f[:-3] for f in os.listdir(os.path.join(root, group))
                  if f.endswith(".py") and not f.startswith("_"))


def load(group: str, name: str, root: str = HERE):
    """The module ``<root>/<group>/<name>.py``, loaded once a process, or
    ``Refused`` naming the kinds there are."""
    path = os.path.join(root, group, f"{name}.py")
    if not (isinstance(name, str) and _NAME.fullmatch(name)
            and os.path.isfile(path)):
        raise Refused(f"{WHAT[group]} {name!r} has no module "
                      f"{group}/{name}.py; {group} there: "
                      f"{', '.join(known(group, root))}")
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            f"bench_{group}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]

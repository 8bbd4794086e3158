"""embed_update_ms: the window divided by the updates completed in it, in
milliseconds (host clock). One update is the mix's update kind
(``bench/updates/<kind>.py``; ``refresh``: fresh weights and a full
``refresh()`` ending in the host table); the window holds the lookups
served between updates too."""


def read(r):
    return r.window_s / r.n_updates * 1e3 if r.n_updates else None

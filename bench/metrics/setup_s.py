"""setup_s: process start to the window's first instant: JAX and the
devices, the graph, the features, the plan, the server's first update
(which compiles, or loads from the persistent cache) and a warm one."""


def read(r):
    return r.setup_s

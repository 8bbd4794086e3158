"""Faults planted under a whole run of the timed path, each of which must
make ``correct`` come out false, and the control in the program's place.

``broken(fault)`` patches the program while a run is made; the rehearsal
tests plant each fault on the CPU, and ``bench/calibrate.py`` runs the
control through the harness on the chip. The benchmark's own runs never
plant one.
"""
from __future__ import annotations

import contextlib
from unittest import mock

import jax
import numpy as np

from bench import graphs, reference

FAULTS = ("none", "unchanged", "half", "exchange", "answer", "control")


@contextlib.contextmanager
def broken(fault: str):
    """The timed path with ``fault`` planted in the program underneath:

    unchanged  after set-up, ``refresh`` leaves the served table as it was
    half       ``scatter`` leaves every other node's row out (zeros)
    exchange   the halo all-to-all delivers nothing
    answer     one lookup's answer is altered where it is produced
    control    the reference in bfloat16 takes the program's place
    """
    from repro.core.partition import ExecutionPlan
    from repro.distributed import halo
    from repro.launch.gnn import GNNServer
    assert fault in FAULTS, fault
    refresh, scatter, query = (GNNServer.refresh, ExecutionPlan.scatter,
                               GNNServer.query)
    calls = {"query": 0}

    def stale_refresh(self):
        return refresh(self) if self.refreshes < 2 else 0.0

    def half_scatter(self, out):
        full = np.array(scatter(self, out))
        full[::2] = 0.0
        return full

    def no_exchange(x_own, *args):
        return jax.numpy.zeros((args[4], x_own.shape[-1]), x_own.dtype)

    def altered_query(self, ids):
        rows = query(self, ids)
        calls["query"] += 1
        if calls["query"] == 3:
            rows = rows.copy()
            rows[0, 0] += 1.0
        return rows

    def control_refresh(self):
        g = self.plan.graph
        csr = {"indptr": g.indptr, "indices": g.indices,
               "deg": np.diff(g.indptr)}
        nbr, wts = graphs.sample_table(csr, g.edge_weight, g.self_loop,
                                       self.plan.sample)
        self.embeddings = reference.forward(g.features, nbr, wts,
                                            [self.params], "bfloat16")[0]
        self.refreshes += 1
        self._served_version = self.version
        return 0.0

    patch = {"none": contextlib.nullcontext(),
             "unchanged": mock.patch.object(GNNServer, "refresh",
                                            stale_refresh),
             "half": mock.patch.object(ExecutionPlan, "scatter",
                                       half_scatter),
             "exchange": mock.patch.object(halo, "_exchange_alltoall",
                                           no_exchange),
             "answer": mock.patch.object(GNNServer, "query", altered_query),
             "control": mock.patch.object(GNNServer, "refresh",
                                          control_refresh)}[fault]
    with patch:
        yield

from .gnn import GNNServer
from .mesh import make_mesh, make_production_mesh
from .steps import (batch_struct, make_prefill_step, make_serve_step,
                    make_train_step)

__all__ = ["GNNServer", "make_mesh", "make_production_mesh",
           "batch_struct", "make_prefill_step", "make_serve_step",
           "make_train_step"]

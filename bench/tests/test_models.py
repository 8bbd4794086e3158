"""The kinds a cell's files name (``bench/kinds.py``): each found by its
file name, a kind with no module refused before set-up, and the ``gcn``
model module's reference against a float64 loop over the slots."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import graphs, harness, kinds, loadgen, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = harness.load_spec(ROOT)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_names_kinds_that_have_modules(cell):
    w = harness.cell_of(SPEC, cell)
    cfg = harness.config_of(SPEC, w["config"], ROOT)
    mix = loadgen.load_mix(w["traffic"])
    model, update = harness.kinds_of(cfg, mix)
    assert model is kinds.load("models", cfg["model"]["kind"])
    assert update is kinds.load("updates", mix["update"]["kind"])
    assert model.JUDGED in model.PRECISIONS
    assert {"float32", "bfloat16"} <= set(model.PRECISIONS)


def _set(path, keys, value):
    with open(path) as f:
        data = json.load(f)
    inner = data
    for k in keys[:-1]:
        inner = inner[k]
    inner[keys[-1]] = value
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.mark.parametrize("file, keys, value, named", [
    ("configs/collab-gcn.json", ["model", "kind"], "gat", "gcn"),
    ("configs/collab-gcn.json", ["numerics"], "crossbar", "ideal"),
    ("traffic/refresh-serve.json", ["update", "kind"], "stream", "refresh"),
])
def test_a_kind_it_cannot_run_is_refused_before_setup(tmp_path, file, keys,
                                                      value, named):
    """A checkout whose cell names a model kind, a numerics or an update
    kind that no module runs: the entry exits non-zero with no result, on
    one line naming the kind and what there is, before it looks for a chip
    (so before set-up). Without the refusal a ``gat`` configuration ran as
    a GCN and read ``correct``."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _set(tmp_path / "bench" / file, keys, value)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "collab-refresh",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    lines = r.stderr.strip().splitlines()
    assert len(lines) == 1, r.stderr
    assert repr(value) in lines[0] and named in lines[0], lines[0]
    assert "not a TPU" not in r.stderr


def test_a_kind_is_found_by_its_file_name(tmp_path):
    """A kind is added as a file: a module written into a ``models``
    directory is found by its name, loaded once, and named when another
    kind is refused."""
    (tmp_path / "models").mkdir()
    (tmp_path / "models" / "toy.py").write_text(
        'NUMERICS = ("ideal",)\nWIDTHS = (3, 2)\n')
    assert kinds.known("models", tmp_path) == ["toy"]
    toy = kinds.load("models", "toy", tmp_path)
    assert toy.WIDTHS == (3, 2)
    assert kinds.load("models", "toy", tmp_path) is toy
    for name in ("gcn", "../models/toy", "toy.py"):
        with pytest.raises(kinds.Refused, match="models there: toy"):
            kinds.load("models", name, tmp_path)


def _float64_loop(x, nbr, wts, params):
    """The GCN of ``bench/models/gcn.py`` in float64, one row and slot at a
    time."""
    h = x.astype(np.float64)
    for li, layer in enumerate(params):
        z = np.zeros_like(h)
        for i in range(len(nbr)):
            for s in range(nbr.shape[1]):
                z[i] += float(wts[i, s]) * h[nbr[i, s]]
        h = z @ np.asarray(layer["w"], np.float64) + np.asarray(
            layer["b"], np.float64)
        if li < len(params) - 1:
            h = np.maximum(h, 0.0)
    return h


# (precision, least and largest RMS gap over the loop's RMS), each from the
# precision's rounding:
#   float32: unit roundoff 2^-24 ~ 6e-8. An output sums 8 slots, then a
#     496-term dot (a 64-term one in layer 2): rounding that adds up as a
#     random walk gives about sqrt(504) x 6e-8 ~ 1.3e-6, the worst case
#     504 x 6e-8 ~ 3e-5. 1e-5 lies between them.
#   stated: Z and W rounded to bfloat16 for the dot, 2^-9 relative at most
#     and 2^-9/sqrt(3) ~ 1.1e-3 in RMS for each; two operands and two
#     layers give about 2.3e-3. Under 5e-4 the bfloat16 rounding is not
#     being done; over 1e-2 something besides rounding differs.
@pytest.mark.parametrize("precision, least, largest", [
    ("float32", 0.0, 1e-5),
    ("stated", 5e-4, 1e-2),
])
def test_gcn_reference_agrees_with_a_float64_loop(precision, least, largest):
    gcn = kinds.load("models", "gcn")
    model = harness.config_of(SPEC, "collab-gcn", ROOT)["model"]
    n, sample = 300, model["sample"]
    g = graphs.csr(n, n * 66, structure_seed=0, min_in_degree=sample - 1)
    ew, sl = gcn.graph_weights(g)
    nbr, wts = graphs.sample_table(g, ew, sl, sample)
    x = graphs.features(graphs.jax_key(2 ** 40 + 5), n, model["in_dim"])
    params = gcn.params_fn(model)(graphs.jax_key(2 ** 40 + 6), np.int32(2))
    [got] = gcn.forward(x, nbr, wts, [params], precision)
    rms, _ = reference.gaps(got, _float64_loop(x, nbr, wts, params))
    assert least <= rms <= largest, rms

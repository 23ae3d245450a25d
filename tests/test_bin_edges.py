"""Bin edges for histogram tree boosting: the 64 exact equal-frequency
edges (``q = j / 63``) of each kind of HIGGS-shaped feature column, the
deployment of the benchmark's ``pctl_k64`` cell
(``bench/configs/higgs_f32_bins63.json``).

The columns come from the cell's own traffic mix at a ragged n (not a
multiple of the kernel's 65,536-element tile, and past ``BINNED_MIN_N``,
so the binned sweeps run), on the jnp path and through the Pallas kernel
in interpret mode.  The cell itself runs end to end on the CPU, cut to the
same n, with its check and its control."""
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import generate, run  # noqa: E402
from bench.entries import _array  # noqa: E402
from repro.core import selection  # noqa: E402

N = 70_001
QS = np.arange(64) / 63
KINDS = ("pt", "eta", "phi", "btag", "mass")
BTAG = np.float32([0.0, 1.0865381, 2.1730762])
SEED = 2**33 + 15015  # past 32 bits: a seed may be any whole number


def _bits(v):
    return np.asarray(v, np.float32).reshape(-1).view(np.uint32)


def _cell():
    return run.load_cell("pctl_k64")


@pytest.fixture(scope="module")
def columns():
    """One column of each kind, as the cell's traffic makes them."""
    first = {}
    for col in _cell()["mix"]["pool"]:
        first.setdefault(col["name"].rstrip("0123456789"), col)
    assert sorted(first) == sorted(KINDS)
    pool = generate.array_pool(SEED, N, "float32", [first[k] for k in KINDS])
    return {k: np.asarray(x) for k, x in zip(KINDS, pool)}


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("kind", KINDS)
def test_bin_edges_are_exact(columns, kind, backend):
    x = columns[kind]
    res = selection.quantiles(jnp.asarray(x), QS, backend=backend)
    got = np.asarray(res.value)
    ranks = _array.ranks_of(QS, N)
    assert ranks[0] == 1 and ranks[-1] == N
    want = np.partition(x.copy(), ranks - 1)[ranks - 1]
    np.testing.assert_array_equal(_bits(got), _bits(want))
    np.testing.assert_array_equal(
        _bits(got), _bits(np.quantile(x, QS, method="inverted_cdf")))
    assert not np.any(np.asarray(res.status) == selection.NOT_CONVERGED)
    if kind == "btag":
        assert np.isin(_bits(got), _bits(BTAG)).all()
        # 32 edges at 0.0, 13 at the middle value, 19 at the top one
        assert [int(np.sum(got == v)) for v in BTAG] == [32, 13, 19]


@pytest.fixture
def no_compile_cache(monkeypatch):
    """The cut cell's programs stay out of the persistent cache."""
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)


def _run_cut(**kw):
    cell = _cell()
    cell["cfg"]["n"] = N
    return run.run(cell, SEED, 0.3, kw.pop("trace", False),
                   require_tpu=False, log=lambda *a: None, **kw)


def test_cut_cell_runs_end_to_end_and_is_correct(no_compile_cache):
    out = _run_cut()
    assert out["correct"] is True
    assert out["checks"]["wrong_answers"] == {"value": 0, "limit": 0}
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"call_ms", "setup_s"}


def test_cut_cell_control_is_wrong(no_compile_cache):
    """The program served bf16 columns, the edges cast back to f32."""
    out = _run_cut(control=True)
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0


def test_cut_cell_traced_run_reads_the_program_counter(no_compile_cache):
    """Without a chip the trace holds no op: the device metrics read
    nothing, the counter the program returns still reads."""
    out = _run_cut(trace=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"multik.live_bracket_pct"}
    assert 0 < out["metrics"]["multik.live_bracket_pct"]["value"] <= 100

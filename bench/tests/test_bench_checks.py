"""The comparisons have teeth: a flipped code, an off-by-one gather and
an unbalanced ledger each read as a failure."""
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
from benchtiny import ROOT, TINY_MODEL  # noqa: E402

sys.path[:0] = [str(ROOT)]
from bench.harness.checks import (Compared, gather_diff,  # noqa: E402
                                  ledger_imbalance)
from bench.harness.loader import load_module  # noqa: E402

RNG = np.random.default_rng(0)


def test_flipped_code_opens_a_gap():
    ref = load_module(ROOT / "bench" / "configs" / "dvqae.py", "reference",
                      "dvqae")
    params = ref.init_params(jax.random.PRNGKey(0), TINY_MODEL)
    x = jnp.asarray(RNG.normal(size=(1, 2, 16, 16, 3)), jnp.float32)
    fn = ref.batched_round(TINY_MODEL, {"lr": 1e-4, "gamma": 0.99})
    own = fn(params, x, jnp.zeros((1, 32), jnp.int32))["codes"]
    same = fn(params, x, own)
    assert float(same["gap_max"].max()) == 0.0
    assert int(same["mismatches"].sum()) == 0
    bad = own.at[0, 5].set((own[0, 5] + 1) % TINY_MODEL["codebook_size"])
    out = fn(params, x, bad)
    assert float(out["gap_max"].max()) > 0
    assert int(out["mismatches"].sum()) == 1


def test_off_by_one_gather_differs():
    table = RNG.normal(size=(16, 8)).astype(np.float32)
    codes = RNG.integers(0, 15, size=100)
    assert gather_diff(table[codes], table[codes]) == {"differ": 0.0,
                                                       "max_abs": 0.0}
    d = gather_diff(table[codes + 1], table[codes])
    assert d["differ"] > 0 and d["max_abs"] > 0
    assert gather_diff(table[codes][:-1], table[codes])["max_abs"] == np.inf


def test_unbalanced_ledger():
    q = SimpleNamespace(bytes_sent=100, bytes_delivered=60, bytes_dropped=8,
                        bytes_rejected=16, bytes_duplicate=8,
                        bytes_in_flight=8)
    assert ledger_imbalance(q) == 0
    q.bytes_delivered += 8
    assert ledger_imbalance(q) == 8


def test_compared_limits():
    assert Compared("x", 0.0, 0).ok and not Compared("x", 1e-9, 0).ok
    assert not Compared("x", float("nan"), 1.0).ok
    assert not Compared("x", float("inf"), 1.0).ok

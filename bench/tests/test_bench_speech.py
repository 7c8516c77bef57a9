"""The speech cell at a size the CPU runs in seconds: the program against
the plain speech reference, a tiny copy of the cell through the run
path, the timed path broken underneath in each way a population cell
can break, and ``work1d.py``'s counts against a hand count."""
import contextlib
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from benchtiny import ROOT, population_metrics, tiny_root  # noqa: E402

sys.path[:0] = [str(ROOT), str(ROOT / "src")]
from bench.harness import work, work1d  # noqa: E402

CELL = "tiny_speech_pop"
TINY_SPEECH = {"kind": "speech", "in_channels": 1, "hidden": 16,
               "n_res_blocks": 1, "latent_dim": 16, "codebook_size": 64,
               "n_groups": 8, "n_slices": 4, "apply_in": True,
               "encoder_in": True, "alpha": 1.0, "beta": 0.25, "lam": 0.01}
CLIP = 1600                     # samples of one clip, 0.1 s at 16 kHz
CLIPS = 4                       # clips per client
LIMITS = {"code_gap_mean": 1e-5, "merge_norm_gap": 1e-5}


def _write(path: Path, obj) -> None:
    path.write_text(json.dumps(obj, indent=2) + "\n")


def speech_root(tmp) -> Path:
    """``tiny_root`` with a tiny speech configuration, its mix, its limits
    and a cell over them, as files and entries of their own."""
    root = tiny_root(tmp)
    bench = root / "bench"
    _write(bench / "configs" / "tiny_speech.json", {
        "name": "tiny_speech", "reference": "dvqae_speech",
        "model": TINY_SPEECH,
        "input": {"samples": CLIP, "channels": 1, "rate_hz": 16000},
        "client": {"lr": 1e-4, "gamma": 0.99}, "samples_per_client": CLIPS,
        "population": {"clients": 40}})
    _write(bench / "traffic" / "tiny_speech_population.json", {
        "driver": "population_speech", "participants": 4, "cohort": 2,
        "pool_clients": 4, "speakers": 3, "words": 5, "check_block": 1})
    _write(bench / "limits" / f"{CELL}.json", LIMITS)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny_speech", "source": "test",
                         "file": "bench/configs/tiny_speech.json",
                         "reduced": [], "why": "tiny"})
    b["workloads"].append({"name": CELL, "config": "tiny_speech",
                           "traffic": "tiny_speech_population", "chips": 1,
                           "why": "tiny"})
    for m in population_metrics(b):
        m["workloads"].append(CELL)
    _write(root / "BENCHMARK.json", b)
    return root


@pytest.fixture
def root(monkeypatch, tmp_path):
    """A tiny copy of the speech cell, with the process's compile cache
    left as it was."""
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: "")
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield speech_root(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


def _run(root, seconds=0.3, seed=3):
    from bench.harness.runner import run
    return run(CELL, seed, seconds, False, started=time.perf_counter(),
               root=root, devs=jax.devices()[:1])


def test_program_matches_the_reference(root):
    """The window's first round against the reference: the same codes,
    the same votes, and the merged dictionary within the limit."""
    from bench.harness.loader import load_cell
    cell = load_cell(CELL, root)
    drv = cell.driver.Driver(cell, 5, LIMITS)
    drv.setup()
    drv.window(0.0, lambda _: contextlib.nullcontext())
    drv.release()
    ans = drv.answers()
    assert ans["codes"].shape == (4, CLIPS * CLIP // 4, 4)
    r = drv.readings(ans)
    assert r["code_mismatch_pct"] == 0.0 and r["code_gap_max"] == 0.0
    assert r["votes_off"] == 0.0
    assert r["merge_norm_gap"] < LIMITS["merge_norm_gap"]
    assert all(c.ok for c in drv.compare(r))


def test_tiny_speech_cell_is_correct(root):
    out = _run(root)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 4 == 0
    assert set(out["metrics"]) == {"clients_per_s",
                                   "uplink_bytes_per_sample", "setup_s"}
    # 400 positions x 4 slices of 3-bit codes per clip: 1,600 codes, 50
    # super-groups of 32 codes in 12 B, 600 B per clip
    assert out["metrics"]["uplink_bytes_per_sample"]["value"] == 600.0
    assert set(out["checks"]) == {"code_gap_mean", "merge_norm_gap",
                                  "votes_off", "uplink_bytes_off",
                                  "versions_off"}


def test_control_is_not_correct(root, monkeypatch):
    from bench.harness.loader import load_cell
    cls = load_cell(CELL, root).driver.Driver
    monkeypatch.setattr(cls, "answers", lambda self: self.control_answers())
    assert _run(root, seed=4)["correct"] is False


@pytest.mark.parametrize("fault", ["finetune_unchanged", "code_altered",
                                   "half_the_cohort"])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    from bench.tools.faults import FAULTS
    FAULTS[fault](monkeypatch.setattr)
    assert _run(root)["correct"] is False


def test_reference_ema_counts_are_the_programs_float32_counts():
    """The speech reference keeps EMA counts in float32 as a client's state
    does: from vote counts as large as a 512,000-code record gives, its
    counts are the program's, bit for bit."""
    from bench.harness.loader import load_module
    from repro.core.ema import ema_update_from_stats, init_ema
    ref = load_module(ROOT / "bench" / "configs" / "dvqae_speech.py",
                      "reference", "dvqae_speech")
    n = np.random.default_rng(0).integers(0, 512_001, (3, 64))
    n[:, :4] = [0, 1, 64_000, 512_000]
    cb = np.ones((64, 16), np.float32)
    state = init_ema(jax.numpy.asarray(cb))
    got = ema_update_from_stats(state, jax.numpy.asarray(n, np.float32),
                                jax.numpy.zeros((3, 64, 16)), gamma=0.99)
    want, _ = ref.ema_weights(n, 0.99)
    np.testing.assert_array_equal(np.asarray(got.counts, np.float64), want)


def test_clips_are_seeded_and_cut_into_cohort_blocks():
    from bench.harness.clips import make_clips
    from bench.harness.images import seed_key
    kw = dict(blocks=3, cohort=2, clips=CLIPS, samples=CLIP, rate=16000,
              speakers=3, words=5)
    a = make_clips(seed_key(2**40 + 7, 2), **kw)
    b = make_clips(seed_key(2**40 + 7, 2), **kw)
    c = make_clips(seed_key(2**40 + 8, 2), **kw)
    assert len(a) == 3 and all(x.shape == (2, CLIPS, CLIP, 1) for x in a)
    for x, y, z in zip(a, b, c):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert not np.array_equal(np.asarray(x), np.asarray(z))
        assert np.isfinite(np.asarray(x)).all()
        assert 0.05 < float(np.asarray(x).std()) < 3.0


# ------------------------------------------------------------ work1d

def test_work1d_layers_and_positions():
    m = TINY_SPEECH                 # C 1, h 16, M 16, one residual block
    # down1: 800 out x 1 x 8 x k4 x 2; down2: 400 x 8 x 16 x 4 x 2;
    # mid 400 x 16 x 16 x 3 x 2; res c1 (k3) and c2 (k1); to_latent k1
    assert work1d.encoder_layers(m, CLIP) == [
        51_200, 409_600, 614_400, 614_400, 204_800, 204_800]
    # from_latent 400 x 16 x 16 x 3 x 2; res; up1 at 800: 16 -> 8, k3;
    # up2 at 1,600: 8 -> 1, k3
    assert work1d.decoder_layers(m, CLIP) == [
        614_400, 614_400, 204_800, 614_400, 76_800]
    assert work1d.positions(m, CLIP) == CLIP // 4 == 400
    assert work1d.positions(m, 16000) == 4000


def test_work1d_client_ops_hand_count():
    m, T = TINY_SPEECH, 400
    enc, dec = 2_099_200, 2_124_800
    # match 2 x 400 x 64 x 16 + GSVQ 2 x 400 x 4 x 64; Eq. 3 average
    # 5 x 400 x 4 slices x 8 atoms x 4 dims
    match = 819_200 + 204_800
    quant = match + 256_000
    encode = match + 400 * 4 * 16
    assert work.quantize_ops(m, T) == quant
    assert work.encode_ops(m, T) == encode
    fwd = enc + dec + quant
    bwd = 2 * (enc + dec) - 51_200
    assert work1d.client_ops(m, CLIP, CLIPS) == CLIPS * (
        fwd + bwd + enc + encode)
    assert work1d.client_ops(m, CLIP, CLIPS) == 4 * 17_049_600

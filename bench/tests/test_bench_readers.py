"""Each per-layer reader under ``bench/metrics/`` on a reduced trace
built by hand: a reader with nothing to read returns None (never 0 for a
share of a peak or of a roofline), and one with something reads a share
within (0, 100]."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]
from bench.harness import loader, work  # noqa: E402
from bench.harness import trace as T  # noqa: E402

ROOT = loader.ROOT
PEAK = json.loads((ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"]
VQ = json.loads((ROOT / "bench" / "configs" / "dvqae_image_vq.json")
                .read_text())["model"]
READERS = sorted(p.stem for p in (ROOT / "bench" / "metrics").glob("*.py"))
SHARES = [n for n in READERS if "roofline" in n or "mfu" in n]


def ctx(busy_ms, kernel_ms, n):
    """One device, a 1 s window, ``n`` clients or records finished."""
    ops = [T.Event("fusion.1", 0, busy_ms * 1e6)]
    ops += [T.Event(k + ".1 = custom-call()", 0, kernel_ms * 1e6)
            for k in ("%encode_codes_pallas", "%decode_codes_pallas")
            if kernel_ms]
    tr = T.Trace(devices={"/device:TPU:0": ops},
                 host=[T.Event(T.WINDOW_SPAN, 0, 1e9)])
    reduced = T.reduce(tr, ["/device:TPU:0"],
                       ["%encode_codes_pallas", "%decode_codes_pallas"])
    P = work.positions(VQ, 64) * 32
    obs = {"clients": n, "elapsed_s": 1.0, "encoded_records": n,
           "record_positions": P, "client_ops": work.client_ops(VQ, 64, 32),
           "offer_s": [1e-4] * n, "decoded_records": n,
           "decode_dispatches": -(-n // 4)}
    return SimpleNamespace(trace=reduced, obs=obs, e2e={}, model=VQ, chips=1,
                           work=work, peak=PEAK)


def reader(name):
    return loader.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                              "metric", name)


@pytest.mark.parametrize("name", SHARES)
def test_a_share_with_nothing_to_read_is_left_out(name):
    assert reader(name).read(ctx(busy_ms=10, kernel_ms=0, n=0)) is None


@pytest.mark.parametrize("name", READERS)
def test_every_reader_reads_a_share_within_bounds(name):
    value = reader(name).read(ctx(busy_ms=900, kernel_ms=400, n=40))
    assert value is not None and value > 0
    if name in SHARES or name.startswith("device_idle"):
        assert value <= 100.0

"""The program's ``octopus/`` spans in a trace: their durations in the
window, idle gaps charged to the innermost span of either prefix, the
sweep that finds it against the benchmark's own lookup, and the
benchmark's reduction left as it was by spans it does not read."""
import random
import sys
import time
from pathlib import Path

import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]
from bench.harness import spans as S  # noqa: E402
from bench.harness import trace as T  # noqa: E402

MS = 1e6
DEV = "/device:TPU:0"
COHORT_SPANS = ["octopus/cohort/deploy", "octopus/cohort/dispatch",
                "octopus/cohort/pull", "octopus/cohort/fold"]
PROGRAM_SPANS = COHORT_SPANS + ["octopus/server/merge"]


def bench_only():
    """A window of 30 ms: ops 0-6, 10-12, 15-16, 19-22 (one round) and
    24-26; ``bench/round`` 0-23."""
    ops = [T.Event("fusion.1", 0 * MS, 4 * MS),
           T.Event("fusion.2", 2 * MS, 4 * MS),
           T.Event("%encode_codes_pallas.3 = custom-call()", 10 * MS, 2 * MS),
           T.Event("fusion.1", 15 * MS, 1 * MS),
           T.Event("fusion.1", 19 * MS, 3 * MS),
           T.Event("fusion.4", 24 * MS, 2 * MS)]
    host = [T.Event(T.WINDOW_SPAN, 0, 30 * MS),
            T.Event("bench/round", 0, 23 * MS)]
    return T.Trace(devices={DEV: ops}, host=host)


def with_program_spans():
    """The same trace with one cohort and a merge inside ``bench/round``
    (deploy 0-1, dispatch 1-2, pull 2-12, fold 12-17, merge 18-21), and
    one cohort that began before the window opened."""
    tr = bench_only()
    tr.host += [T.Event("octopus/cohort", -5 * MS, 4 * MS),
                T.Event("octopus/cohort/pull", -4 * MS, 2 * MS),
                T.Event("octopus/cohort", 0, 17 * MS),
                T.Event("octopus/cohort/deploy", 0, 1 * MS),
                T.Event("octopus/cohort/dispatch", 1 * MS, 1 * MS),
                T.Event("octopus/cohort/pull", 2 * MS, 10 * MS),
                T.Event("octopus/cohort/fold", 12 * MS, 5 * MS),
                T.Event("octopus/server/merge", 18 * MS, 3 * MS)]
    return tr


def test_durations_of_spans_that_start_in_the_window():
    r = S.reduce(with_program_spans(), DEV)
    assert r.durations == pytest.approx({
        "octopus/cohort": [0.017], "octopus/cohort/deploy": [0.001],
        "octopus/cohort/dispatch": [0.001], "octopus/cohort/pull": [0.010],
        "octopus/cohort/fold": [0.005], "octopus/server/merge": [0.003]})


def test_idle_gaps_go_to_the_innermost_program_span():
    r = S.reduce(with_program_spans(), DEV)
    gaps = dict(r.idle_gaps)
    # by each gap's middle: 6-10 in pull, 12-15 in fold, 16-19 between
    # fold and merge (bench/round), 22-24 and 26-30 outside any span
    assert gaps == pytest.approx({
        "octopus/cohort/pull": 0.004, "octopus/cohort/fold": 0.003,
        "bench/round": 0.003, T.NO_SPAN: 0.006})
    assert r.idle_s == pytest.approx(0.016)
    assert r.program_idle_share == pytest.approx(7 / 16)


def test_the_benchmark_reduction_is_unmoved_by_program_spans():
    kernels = ["%encode_codes_pallas"]
    plain = T.reduce(bench_only(), [DEV], kernels)
    spanned = T.reduce(with_program_spans(), [DEV], kernels)
    assert spanned.window_s == plain.window_s
    assert spanned.busy_s == plain.busy_s
    assert spanned.kernel_ns == plain.kernel_ns
    assert spanned.device_ops == plain.device_ops
    assert spanned.idle_share == plain.idle_share


def test_without_program_spans_gaps_match_the_benchmark_reduction():
    tr = bench_only()
    r = S.reduce(tr, DEV)
    assert r.durations == {}
    assert r.idle_gaps == T.reduce(tr, [DEV]).idle_gaps
    assert r.window_s == T.reduce(tr, [DEV]).window_s


def test_sweep_agrees_with_the_scan_on_overlapping_spans():
    rng = random.Random(7)
    host = [T.Event(T.WINDOW_SPAN, 0, 1000)]
    for i in range(300):
        s = rng.randrange(0, 1000)
        host.append(T.Event(f"s{i}", s, rng.choice([1, 5, 20, 100, 400])))
    points = [rng.uniform(-10, 1010) for _ in range(2000)]
    assert S.innermost(host, points) == [T._innermost(host, t)
                                         for t in points]


def test_1e5_ops_and_1e3_spans_reduce_in_under_two_seconds():
    """10^5 device ops and 10^3 program spans nested four deep."""
    ops = [T.Event("fusion.1", i * 10_000, 6_000) for i in range(100_000)]
    host = [T.Event(T.WINDOW_SPAN, 0, 1e9), T.Event("bench/round", 0, 1e9)]
    step = 1e9 / 200
    for c in range(200):
        lo = c * step
        host.append(T.Event("octopus/cohort", lo, step))
        host += [T.Event(n, lo + k * step / 4, step / 4)
                 for k, n in enumerate(COHORT_SPANS)]
    tr = T.Trace(devices={DEV: ops}, host=host)
    t0 = time.perf_counter()
    r = S.reduce(tr, DEV)
    assert time.perf_counter() - t0 < 2.0
    assert len(r.durations["octopus/cohort/pull"]) == 200
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(0.4)


@pytest.mark.parametrize("name", PROGRAM_SPANS)
def test_median_in_ms_or_none_when_absent(name):
    tr = bench_only()
    r = S.reduce(tr, DEV)
    assert r.median_ms(name) is None
    tr.host += [T.Event(name, a * MS, d * MS)
                for a, d in ((1, 2.0), (5, 4.0), (11, 9.0))]
    assert S.reduce(tr, DEV).median_ms(name) == pytest.approx(4.0)


def test_from_xplane_keeps_both_prefixes(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation(T.WINDOW_SPAN):
            with TraceAnnotation("octopus/cohort", cohort=0):
                with TraceAnnotation("octopus/cohort/pull"):
                    pass
            with TraceAnnotation("other/span"):
                pass
    finally:
        jax.profiler.stop_trace()
    names = [e.name for e in S.from_xplane(str(tmp_path)).host]
    assert sorted(names) == [T.WINDOW_SPAN, "octopus/cohort",
                             "octopus/cohort/pull"]

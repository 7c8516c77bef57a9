"""Flight recorder + metrics plane (repro.obs).

The load-bearing contract is NEUTRALITY: tracing must observe the
pipeline without perturbing it. With a recorder installed, cohort round
words/features stay bit-identical, scheduler draws are unchanged (same
seeds as the determinism tests), and the counted fused-dispatch numbers
match the PR-4/PR-5 regression baselines. The recorder itself must obey
§2.5 — packed words, labels and latents never enter the trace, only
payload METADATA.
"""
import glob
import json

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import octopus as OC
from repro.core.dvqae import DVQAEConfig
from repro.obs import report as obs_report
from repro.sim import CohortEngine, CohortPlan
from repro.wire import OctopusServer

N_CLIENTS = 12


@pytest.fixture(autouse=True)
def no_ambient_recorder():
    """Tests own the recorder lifecycle — drop any env-installed one."""
    obs.uninstall()
    yield
    obs.uninstall()


@pytest.fixture(scope="module")
def tiny_cfg():
    return DVQAEConfig(kind="image", in_channels=3, hidden=8, latent_dim=8,
                       codebook_size=16, n_res_blocks=1)


@pytest.fixture(scope="module")
def server(tiny_cfg):
    return OC.server_init(jax.random.PRNGKey(0), tiny_cfg)


@pytest.fixture(scope="module")
def data():
    return jax.random.normal(jax.random.PRNGKey(1),
                             (N_CLIENTS, 2, 8, 8, 3))


def _data_fn(data):
    return lambda ids: data[np.asarray(ids)]


# ------------------------------------------------------------ zero-overhead

def test_recorder_is_off_by_default():
    assert obs.active() is None


def test_recording_scopes_the_singleton(tmp_path):
    path = tmp_path / "t.jsonl"
    with obs.recording(path) as rec:
        assert obs.active() is rec
        rec.event("merge", version=1)
        with rec.span("decode", version=0):
            pass
    assert obs.active() is None
    events = obs_report.load_events(str(path))
    assert [e["kind"] for e in events] == ["merge", "decode"]
    assert events[1]["dur_ms"] >= 0.0
    assert [e["seq"] for e in events] == [0, 1]


def test_install_from_env(tmp_path, monkeypatch):
    path = tmp_path / "env.jsonl"
    monkeypatch.setenv(obs.ENV_VAR, str(path))
    rec = obs.install_from_env()
    try:
        assert obs.active() is rec and rec.path == str(path)
        # idempotent while one is installed
        assert obs.install_from_env() is rec
    finally:
        obs.uninstall()
        rec.close()


# ------------------------------------------------------- tracing neutrality

def test_facade_round_bit_identical_with_tracing(tiny_cfg, server, data,
                                                 tmp_path):
    srv = OctopusServer(server, tiny_cfg)
    batch = data[0]
    plain = srv.deploy().round(batch)
    with obs.recording(tmp_path / "t.jsonl"):
        traced = srv.deploy().round(batch)
    np.testing.assert_array_equal(np.asarray(plain.payload),
                                  np.asarray(traced.payload))
    assert plain.nbytes == traced.nbytes
    assert plain.shape == traced.shape


def test_cohort_round_bit_identical_with_tracing(tiny_cfg, server, data,
                                                 tmp_path):
    """Streamed round words + merged features are unchanged by tracing."""
    engine = CohortEngine(tiny_cfg, gamma=0.9, n_local_steps=0)
    plan = CohortPlan.build(np.arange(N_CLIENTS), 5)
    plain = engine.round(server, plan, _data_fn(data))
    with obs.recording(tmp_path / "t.jsonl") as rec:
        traced = engine.round(server, plan, _data_fn(data))
    np.testing.assert_array_equal(plain.stats.num, traced.stats.num)
    np.testing.assert_array_equal(plain.stats.den, traced.stats.den)
    for a, b in zip(plain.payloads, traced.payloads):
        np.testing.assert_array_equal(np.asarray(a.payload),
                                      np.asarray(b.payload))
    fa = OC.codes_to_features(server, tiny_cfg, plain.payloads[0])
    fb = OC.codes_to_features(server, tiny_cfg, traced.payloads[0])
    np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    # one encode event per cohort, metadata matching the payloads
    events = obs_report.load_events(str(tmp_path / "t.jsonl"))
    enc = [e for e in events if e["kind"] == "encode"]
    assert len(enc) == plan.n_cohorts
    assert [e["nbytes"] for e in enc] == [p.nbytes for p in traced.payloads]
    assert rec.n_events == len(events)


def test_scheduler_draws_unchanged_with_recorder(tmp_path):
    """Reuses the determinism test's seeds: a recorder must not touch the
    per-purpose RNG substreams."""
    from repro.server import RoundScheduler, SchedulerConfig
    cfg = SchedulerConfig(participation=0.5, straggler_prob=0.5, max_delay=3,
                          drop_prob=0.2, leave_prob=0.3, join_prob=0.4)

    def trace(key):
        s = RoundScheduler(16, cfg, key=key)
        return [s.step() for _ in range(12)]

    plain = trace(jax.random.PRNGKey(5))
    with obs.recording(tmp_path / "t.jsonl"):
        traced = trace(jax.random.PRNGKey(5))
    for ea, eb in zip(plain, traced):
        for fa, fb in zip(ea, eb):
            np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_traffic_run_identical_with_tracing(tiny_cfg, data, tmp_path):
    """The replay-determinism run (same seeds as tests/test_cohort.py)
    with tracing on: identical ledger/codebooks/features, and the trace's
    per-round Σ-bytes equal the §2.8 accounting bit-exactly."""
    from repro.server import RoundScheduler, SchedulerConfig

    def go():
        state = OC.server_init(jax.random.PRNGKey(0), tiny_cfg)
        wire = OctopusServer(state, tiny_cfg)
        sched = RoundScheduler(
            N_CLIENTS, SchedulerConfig(participation=0.5,
                                       straggler_prob=0.4, drop_prob=0.2),
            key=jax.random.PRNGKey(11))
        engine = CohortEngine(tiny_cfg, gamma=0.9, n_local_steps=0)
        hist = engine.run_traffic(wire, sched, _data_fn(data),
                                  cohort_size=3, n_rounds=4, merge_every=2)
        return wire, hist

    wa, ha = go()
    trace_path = tmp_path / "traffic.jsonl"
    with obs.recording(trace_path):
        wb, hb = go()
    assert ha == hb
    np.testing.assert_array_equal(np.asarray(wa.registry.current),
                                  np.asarray(wb.registry.current))
    assert wa.store.total_bytes == wb.store.total_bytes
    fa, _ = wa.features()
    fb, _ = wb.features()
    np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))

    # §2.8 accounting INSIDE the trace: per-round uplink-event Σ-nbytes
    # == the round ledger's bytes_sent == the TrafficRound ledger
    summary = obs_report.summarize(obs_report.load_events(str(trace_path)))
    assert obs_report.check_bytes(summary) == []
    by_round = {r["round"]: r for r in summary["rounds"]}
    for h in hb:
        assert by_round[h.round]["uplink_bytes"] == h.bytes_sent
        assert by_round[h.round]["bytes_sent"] == h.bytes_sent
    assert summary["uplinks"]["bytes"] == sum(h.bytes_sent for h in hb)
    assert summary["merges"] and len(summary["rounds"]) == 4


# ------------------------------------------------------- dispatch monitor

def test_dispatch_monitor_matches_regression_baselines(tiny_cfg, server,
                                                       data, tmp_path):
    """PR-4/PR-5 baseline: one facade round = exactly ONE encoder pass
    and ONE fused encode dispatch — with tracing on AND off."""
    srv = OctopusServer(server, tiny_cfg)
    batch = data[0]
    with obs.dispatch_monitor() as plain:
        srv.deploy().round(batch, finetune=0)
    with obs.recording(tmp_path / "t.jsonl") as rec:
        with obs.dispatch_monitor() as traced:
            srv.deploy().round(batch, finetune=0)
    for counts in (plain, traced):
        assert (counts.encoder_passes, counts.encode_dispatches) == (1, 1)
        assert counts.pack_dispatches == 0      # fused pack, no extra hop
    # non-zero counts folded into the active recorder's metrics
    snap = rec.metrics.snapshot()["counters"]
    assert snap["encoder_passes"] == 1 and snap["encode_dispatches"] == 1


def test_dispatch_monitor_restores_originals():
    from repro.core import dvqae
    from repro.kernels import ops
    before = (dvqae.encode, ops.encode_codes, ops.decode_codes,
              ops.pack_codes, ops.unpack_codes)
    with pytest.raises(RuntimeError):
        with obs.dispatch_monitor():
            raise RuntimeError("boom")
    assert (dvqae.encode, ops.encode_codes, ops.decode_codes,
            ops.pack_codes, ops.unpack_codes) == before


def test_dispatch_monitor_counts_decode_and_pack(tiny_cfg):
    import jax.numpy as jnp
    from repro.kernels import ops
    idx = jnp.arange(16, dtype=jnp.int32) % 4
    with obs.dispatch_monitor() as counts:
        words = ops.pack_codes(idx, bits=2)
        ops.unpack_codes(words, bits=2, count=16)
    assert counts.pack_dispatches == 1
    assert counts.unpack_dispatches == 1
    assert counts.encoder_passes == 0


# -------------------------------------------------------- §2.5 in the trace

def test_trace_never_carries_words_or_labels(tiny_cfg, server, data,
                                            tmp_path):
    """Metadata-only capture, enumerated over EVERY event kind: no event
    field holds the packed words, a label channel, or anything
    array-shaped. The kind list comes from ``obs.EVENT_KINDS`` at
    runtime, so a newly added event type lands in this scan the moment
    it exists — it cannot silently start carrying words or latents."""
    srv = OctopusServer(server, tiny_cfg)
    batch = data[0]
    labels = {"content": np.arange(batch.shape[0], dtype=np.int32)}
    with obs.recording(tmp_path / "t.jsonl") as rec:
        p = srv.deploy().round(batch, labels=labels)
        srv.ingest(p)
        srv.features()
        # synthesize one event of every registered kind with payload
        # metadata attached — the §2.5 scan below must hold for ALL of
        # them, including kinds no pipeline call emitted above
        for kind in obs.EVENT_KINDS:
            rec.event(kind, **obs.payload_meta(p))
    seen = set()
    for ev in obs_report.load_events(str(tmp_path / "t.jsonl")):
        seen.add(ev["kind"])
        assert "payload" not in ev and "words" not in ev
        assert "labels" not in ev and "content" not in ev
        for v in ev.values():
            assert isinstance(v, (int, float, bool, str, type(None)))
    assert seen >= set(obs.EVENT_KINDS)       # every kind was scanned
    meta = obs.payload_meta(p)
    assert set(meta) == set(obs.PAYLOAD_META_FIELDS)
    assert meta["nbytes"] == p.nbytes and meta["privatized"] is True


def test_event_refuses_arrays_and_containers(tmp_path):
    """The recorder enforces §2.5 mechanically: array- or
    container-valued event fields raise, for every event kind — new
    call sites cannot leak words/labels even by mistake."""
    with obs.recording(tmp_path / "t.jsonl") as rec:
        for kind in obs.EVENT_KINDS:
            for bad in (np.arange(4), [1, 2], (1, 2), {"y": 1}, b"words"):
                with pytest.raises(ValueError, match="scalar-only"):
                    rec.event(kind, leak=bad)
        ok = rec.event("tap", n=3, f=1.5, s="x", b=True, none=None,
                       np_scalar=np.float32(2.0))
        assert ok["n"] == 3
    events = obs_report.load_events(str(tmp_path / "t.jsonl"))
    assert [e["kind"] for e in events] == ["tap"]   # refused != written


# ----------------------------------------------------------- report CLI

def test_report_cli_check_and_json(tiny_cfg, data, tmp_path, capsys):
    from repro.server import RoundScheduler, SchedulerConfig
    state = OC.server_init(jax.random.PRNGKey(0), tiny_cfg)
    wire = OctopusServer(state, tiny_cfg)
    sched = RoundScheduler(
        N_CLIENTS, SchedulerConfig(participation=0.5, straggler_prob=0.4,
                                   drop_prob=0.2),
        key=jax.random.PRNGKey(11))
    engine = CohortEngine(tiny_cfg, gamma=0.9, n_local_steps=0)
    trace = tmp_path / "t.jsonl"
    with obs.recording(trace):
        hist = engine.run_traffic(wire, sched, _data_fn(data),
                                  cohort_size=3, n_rounds=4, merge_every=2)
        wire.features()

    out_json = tmp_path / "rep.json"
    rc = obs_report.main([str(trace), "--check", "--json", str(out_json)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "bytes check OK" in text and "uplinks:" in text
    rep = json.loads(out_json.read_text())
    assert rep["section"] == "obs" and rep["bytes_check_ok"] is True
    rows = {r["name"]: r for r in rep["rows"]}
    # BENCH-style: real JSON numbers, extra the only string field
    for r in rep["rows"]:
        assert isinstance(r["value"], (int, float))
        assert isinstance(r["extra"], str)
    assert rows["rounds"]["value"] == 4
    # the report's measured Σ-bytes reproduce the traffic ledger
    assert rows["uplink_bytes"]["value"] == sum(h.bytes_sent for h in hist)
    assert any(n.startswith("decode_v") for n in rows)


def test_report_check_fails_on_tampered_ledger(tmp_path):
    trace = tmp_path / "bad.jsonl"
    events = [
        {"kind": "uplink", "round": 0, "nbytes": 8},
        {"kind": "round", "round": 0, "bytes_sent": 12, "dur_ms": 1.0},
    ]
    trace.write_text("\n".join(json.dumps(e) for e in events) + "\n")
    summary = obs_report.summarize(obs_report.load_events(str(trace)))
    assert obs_report.check_bytes(summary)
    assert obs_report.main([str(trace), "--check"]) == 1
    # an EMPTY trace is not evidence either
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert obs_report.main([str(empty), "--check"]) == 1


# ----------------------------------------------------------- metrics plane

def test_metrics_registry_instruments():
    m = obs.MetricsRegistry()
    m.inc("uplinks", 3)
    m.inc("uplinks")
    m.set_gauge("depth", 7)
    snap = m.snapshot()
    assert snap["counters"]["uplinks"] == 4
    assert snap["gauges"]["depth"] == 7


def test_queue_and_store_metrics(tiny_cfg, server, data, tmp_path):
    from repro.server.runtime import UplinkQueue
    srv = OctopusServer(server, tiny_cfg)
    with obs.recording(tmp_path / "t.jsonl") as rec:
        p = srv.deploy().round(data[0])
        q = UplinkQueue()
        q.send(p, round=0, delay=1)
        assert rec.metrics.gauge("uplink_queue_depth").value == 1
        q.deliver(srv, 1)
        assert rec.metrics.gauge("uplink_queue_depth").value == 0
        assert rec.metrics.gauge("store_records").value == 1
        assert rec.metrics.gauge("store_bytes").value == p.nbytes
    events = obs_report.load_events(str(tmp_path / "t.jsonl"))
    kinds = [e["kind"] for e in events]
    assert kinds.count("uplink") == 2     # facade round + queue.send
    assert "ingest" in kinds


# ------------------------------------------------------------ program spans

COHORT_CHILDREN = ("deploy", "dispatch", "pull", "fold")


def _host_spans(directory):
    """(name, start_ns, end_ns, stats) of every ``octopus/`` and
    ``bench/`` host event in the newest profile under ``directory``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    data = ProfileData.from_file(path[-1])
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats))
                   for plane in data.planes if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith((obs.SPAN_PREFIX, "bench/"))),
                  key=lambda s: s[1])


def test_cohort_round_spans_on_the_profiler_clock(tiny_cfg, server, data,
                                                  tmp_path):
    """Under a profiler session each cohort is one ``octopus/cohort``
    span holding deploy, dispatch, pull and fold once each, in order;
    the server merge is one ``octopus/server/merge``; and the round's
    outputs are bit-identical to an unprofiled round."""
    engine = CohortEngine(tiny_cfg, gamma=0.9, n_local_steps=0)
    plan = CohortPlan.build(np.arange(N_CLIENTS), 5)
    plain = engine.round(server, plan, _data_fn(data), version=0)
    wire = OctopusServer(server, tiny_cfg)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench/window"):
            traced = engine.round(server, plan, _data_fn(data), version=0)
            merged = wire.merge_stats(traced.stats)
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(plain.stats.num, traced.stats.num)
    np.testing.assert_array_equal(plain.stats.den, traced.stats.den)
    for a, b in zip(plain.payloads, traced.payloads):
        np.testing.assert_array_equal(np.asarray(a.payload),
                                      np.asarray(b.payload))

    spans = _host_spans(tmp_path)
    window = [s for s in spans if s[0] == "bench/window"]
    assert len(window) == 1
    program = [s for s in spans if s[0].startswith(obs.SPAN_PREFIX)]
    expected = {"octopus/cohort", "octopus/server/merge"} | {
        f"octopus/cohort/{c}" for c in COHORT_CHILDREN}
    assert {s[0] for s in program} == expected    # nothing else octopus/
    cohorts = [s for s in program if s[0] == "octopus/cohort"]
    # 2 images of 8x8 per client: 2 x (8/4)^2 latent positions
    assert [c[3] for c in cohorts] == [
        {"cohort": i, "clients": int(c.size), "version": 0, "positions": 8}
        for i, c in enumerate(plan.cohorts)]
    for _, lo, hi, _ in cohorts:
        assert window[0][1] <= lo and hi <= window[0][2]
        inside = [s for s in program
                  if s[0].startswith("octopus/cohort/") and lo <= s[1]
                  and s[2] <= hi]
        assert [s[0] for s in inside] == [f"octopus/cohort/{c}"
                                          for c in COHORT_CHILDREN]
        assert all(a[2] <= b[1] for a, b in zip(inside, inside[1:]))
    merges = [s for s in program if s[0] == "octopus/server/merge"]
    assert len(merges) == 1 and merges[0][3] == {"version": 0}
    assert merged == 1 and merges[0][1] >= cohorts[-1][2]


@pytest.mark.parametrize("kind,sample,groups,per_sample", [
    ("image", (8, 8, 3), 1, (8 // 4) * (8 // 4)),
    ("speech", (64, 1), 4, 64 // 4)])
def test_cohort_span_counts_latent_positions(kind, sample, groups,
                                             per_sample, tmp_path):
    """``positions`` of each ``octopus/cohort`` span is the latent
    positions of one client record, from the data's shape: images x
    H/4 x W/4 for an image cohort, clips x T/4 for a speech cohort (here
    with GSVQ, whose codes carry a slice axis too)."""
    cfg = DVQAEConfig(kind=kind, in_channels=sample[-1], hidden=8,
                      latent_dim=8, codebook_size=16, n_res_blocks=1,
                      n_groups=groups, n_slices=2 if groups > 1 else 1)
    srv = OC.server_init(jax.random.PRNGKey(0), cfg)
    per_client = 3
    x = jax.random.normal(jax.random.PRNGKey(1), (4, per_client) + sample)
    engine = CohortEngine(cfg, gamma=0.9, n_local_steps=0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.round(srv, CohortPlan.build(np.arange(4), 2), _data_fn(x))
    finally:
        jax.profiler.stop_trace()
    cohorts = [s for s in _host_spans(tmp_path) if s[0] == "octopus/cohort"]
    assert [c[3]["positions"] for c in cohorts] == \
        [per_client * per_sample] * 2


@pytest.mark.parametrize("bad", [np.arange(4), [1, 2], (1, 2), {"y": 1},
                                 b"words"])
def test_span_refuses_non_scalar_args(bad, tmp_path):
    with pytest.raises(ValueError, match="scalar-only"):
        obs.span("cohort", leak=bad)
    with obs.recording(tmp_path / "t.jsonl") as rec:
        with pytest.raises(ValueError, match="scalar-only"):
            rec.span("decode", leak=bad)
        with pytest.raises(ValueError, match="scalar-only"):
            with obs.span("cohort", event="encode") as s:
                s.add(leak=bad)
    assert obs_report.load_events(str(tmp_path / "t.jsonl")) == []


def test_span_writes_an_event_only_when_asked_and_recording(tmp_path):
    """No recorder: a span writes nothing, ``event`` or not. With one
    installed, only a span given ``event`` writes, once, with its args,
    the fields added inside the block and the block's ``dur_ms``."""
    with obs.span("cohort", event="encode", cohort=0) as s:
        assert not s.recording
        s.add(nbytes=8)
    path = tmp_path / "t.jsonl"
    with obs.recording(path) as rec:
        with obs.span("cohort/pull") as s:
            assert not s.recording
        with obs.span("cohort", event="encode", cohort=2, clients=5) as s:
            assert s.recording
            s.add(nbytes=8)
        with rec.span("decode", version=3):
            pass
    events = obs_report.load_events(str(path))
    assert [e["kind"] for e in events] == ["encode", "decode"]
    assert {k: events[0][k] for k in ("cohort", "clients", "nbytes")} == \
        {"cohort": 2, "clients": 5, "nbytes": 8}
    assert events[0]["dur_ms"] >= 0.0 and events[1]["version"] == 3
    # one span implementation: the recorder's span is obs.span's type
    assert type(rec.span("decode")) is type(obs.span("cohort"))

"""Ways to break the timed path underneath a run, one function each.

The tests use them to show that ``correct`` comes out false, and
``calibrate.py --faults`` reads each on the chip. Each takes
``patch(obj, name, value)``: ``monkeypatch.setattr`` in a test, or
``Patcher`` below, which undoes its patches on exit.
"""
from __future__ import annotations


class Patcher:
    """``setattr`` that remembers what it replaced and restores it."""

    def __init__(self):
        self.saved = []

    def __call__(self, obj, name, value):
        self.saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        for obj, name, old in reversed(self.saved):
            setattr(obj, name, old)
        self.saved.clear()
        return False


def merge_unchanged(patch):
    """A step that returns its state unchanged: the merge keeps the
    deployed codebook."""
    from repro.core import octopus as OC
    patch(OC, "server_merge_stats", lambda server, stats: server)


def finetune_unchanged(patch):
    """A step that returns its state unchanged: each client's local
    fine-tune step keeps the deployed weights."""
    from repro.core import octopus as OC
    patch(OC, "client_finetune_step",
          lambda client, cfg, batch, lr=None, opt=None: (client, opt, None))


def half_the_cohort(patch):
    """Half of the batch left out, the mean over the rest: each cohort's
    merge statistics come from its first half of clients."""
    import repro.sim.cohort as cohort
    real = cohort.merge_stats
    patch(cohort, "merge_stats", lambda cbs, counts, **kw:
          real(cbs[: len(cbs) // 2], counts[: len(cbs) // 2], **kw))


def code_altered(patch):
    """An answer altered where it is produced: one code changed in the
    uplink words of every cohort."""
    from repro.sim.engine import SimEngine
    real = SimEngine.round

    def round_(self, *a, **kw):
        clients, p = real(self, *a, **kw)
        return clients, p._replace(payload=p.payload.at[0, 0].add(1))
    patch(SimEngine, "round", round_)


def exchange_left_out(patch):
    """The exchange between chips left out: only the first chip's
    records reach the server, the other chips' words arrive as zeros."""
    from repro.sim.engine import SimEngine
    real = SimEngine.round

    def round_(self, *a, **kw):
        clients, p = real(self, *a, **kw)
        w = p.payload
        return clients, p._replace(payload=w.at[w.shape[0] // 4:].set(0))
    patch(SimEngine, "round", round_)


def decode_altered(patch):
    """An answer altered where it is produced: one decoded value."""
    import repro.server.store as store
    real = store.decode_group

    def decode(recs, *a, **kw):
        return [b.at[0, 0, 0].add(1e-3) for b in real(recs, *a, **kw)]
    patch(store, "decode_group", decode)


def half_decoded(patch):
    """Half of the batch left out: the decoder takes the first half of
    each batch it is handed and drops the rest."""
    from repro.server.runtime import ContinuousIngestService
    real = ContinuousIngestService._bulk_decode

    def bulk(self, records):
        return real(self, records[: len(records) // 2])
    patch(ContinuousIngestService, "_bulk_decode", bulk)


def tick_unchanged(patch):
    """A step that returns its state unchanged: a tick that delivers
    nothing."""
    from repro.server.runtime import UplinkQueue
    patch(UplinkQueue, "deliver",
          lambda self, wire, round, results=None: (0, 0))


FAULTS = {f.__name__: f for f in (merge_unchanged, finetune_unchanged,
                                  half_the_cohort,
                                  code_altered, exchange_left_out,
                                  decode_altered, half_decoded,
                                  tick_unchanged)}

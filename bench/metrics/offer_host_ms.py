"""Median host time of one ``ContinuousIngestService.offer`` call (the
benchmark's clock around each call, traced run): admission's cost on
the host, CRC check included."""

import numpy as np


def read(ctx):
    s = ctx.obs["offer_s"]
    return float(np.median(s)) * 1e3 if len(s) else None

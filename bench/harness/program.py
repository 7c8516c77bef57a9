"""The few adapters between the benchmark's data and the program under
test: its configuration object and its server state around the weights
the benchmark made."""
from __future__ import annotations

import jax.numpy as jnp

MODEL_KEYS = ("kind", "in_channels", "hidden", "n_res_blocks", "latent_dim",
              "codebook_size", "n_groups", "n_slices", "apply_in",
              "encoder_in", "alpha", "beta", "lam")


def program_config(model: dict):
    """The program's ``DVQAEConfig`` for a configuration file's model."""
    from repro.core.dvqae import DVQAEConfig
    return DVQAEConfig(**{k: model[k] for k in MODEL_KEYS})


def server_state(params):
    """A fresh ``ServerState`` around the benchmark's seeded weights."""
    from repro.core import octopus as OC
    from repro.optim.adamw import adamw_init
    return OC.ServerState(params=params, opt=adamw_init(params),
                          step=jnp.zeros((), jnp.int32))

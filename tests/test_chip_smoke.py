"""``chip_smoke.py``'s phases at a tiny config on CPU (interpret mode /
jnp oracle), so the chip smoke run cannot rot between chip runs: every
phase's reference checks and the ingest byte ledger run here too. The
TPU-only parts (platform guard, Mosaic kernels in the lowered programs)
stay in ``main``."""
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.core.dvqae import DVQAEConfig  # noqa: E402

TINY = chip_smoke.Sizes(
    cfg=DVQAEConfig(hidden=8, n_res_blocks=1, latent_dim=8,
                    codebook_size=16),
    image=8, atd_images=32, pretrain_steps=6, pretrain_batch=8,
    n_clients=3, per_client=2, pop_clients=9, cohort=4, head_steps=3,
    head_batch=4)


@pytest.fixture(scope="module")
def ctx():
    c = chip_smoke.Ctx(TINY, seed=0)
    chip_smoke.run_phases(c, chip_smoke.PHASES)
    return c


def test_phases_pass_reference_checks(ctx):
    """All six phases ran; their encode/decode reference checks passed."""
    assert len(ctx.payloads) == TINY.n_clients
    assert ctx.srv.version == 1                  # the population merge


def test_ingest_ledger_balances(ctx):
    nbytes = sum(p.nbytes for p in ctx.payloads)
    assert ctx.srv.store.ingested_bytes == nbytes
    assert len(ctx.srv.store) == TINY.n_clients


def test_encode_check_flags_a_wrong_code(ctx):
    """The encode reference check has teeth: one flipped code far from
    any near-tie fails it."""
    from repro.core import octopus as OC
    from repro.wire import CodePayload
    cfg, cl, d = ctx.cfg, ctx.clients[0], ctx.batches[0]
    z, _ = OC.client_encode(cl.state.params, cfg, d.x)
    p = ctx.payloads[0]
    idx = np.asarray(p.unpack()).copy()
    # the round refreshed the client codebook; check against the
    # deployed one the codes were packed under
    cb = ctx.srv.registry.get(p.version)
    assert chip_smoke.encode_check(cfg, z, cb, p)["mismatches"] == 0
    idx.reshape(-1)[0] = (idx.reshape(-1)[0] + 1) % cfg.codebook_size
    bad = CodePayload.pack(idx, bits=p.bits)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.encode_check(cfg, z, cb, bad)


def test_sharded_phase_matches_one_device():
    c = chip_smoke.Ctx(TINY._replace(cohort=4, per_client=2), seed=1)
    chip_smoke.run_phases(c, chip_smoke.SHARDED_PHASES)


def test_main_refuses_without_tpu():
    """Under JAX_PLATFORMS=cpu the script exits non-zero and prints no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


_COMPILE_ONE = (
    "import sys; sys.path.insert(0, {src!r})\n"
    "from repro.launch.compile_cache import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "import jax, jax.numpy as jnp\n"
    "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n")


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR set: entries land there and the repo
    cache stays untouched. Unset: the fixed ``<repo>/.jax_cache``."""
    from repro.launch.compile_cache import ENV, REPO_CACHE
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop(ENV, None)
    if from_env:
        env[ENV] = str(tmp_path)
    before = set(REPO_CACHE.iterdir()) if REPO_CACHE.exists() else set()
    out = subprocess.run(
        [sys.executable, "-c",
         _COMPILE_ONE.format(src=os.path.join(ROOT, "src"))],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    where = out.stdout.strip().splitlines()[-1]
    after = set(REPO_CACHE.iterdir()) if REPO_CACHE.exists() else set()
    if from_env:
        assert where == str(tmp_path)
        assert any(tmp_path.iterdir())
        assert after == before
    else:
        assert where == str(REPO_CACHE)
        assert any(p.name.endswith("-cache") for p in after)

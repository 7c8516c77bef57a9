import jax
import pytest

# Tests run on the single CPU device (dry-run owns the 512-device trick).
jax.config.update("jax_platform_name", "cpu")


def pytest_configure(config):
    # Escalate the repro deprecation shims (PackedCodes, client_transmit,
    # IngestBuffer, ...) to errors: no internal code path may silently
    # construct a deprecated carrier. Every shim's message says which
    # repro.* replacement to use, which is what the filter keys on.
    # (Tests that exercise the shims on purpose use pytest.warns, which
    # overrides these filters inside its block.)
    config.addinivalue_line(
        "filterwarnings", r"error:.*use repro\.:DeprecationWarning")


def abstract_mesh(sizes, names):
    """A device-free mesh of the given axis sizes and names."""
    return jax.sharding.AbstractMesh(sizes, names)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)

"""Test configuration.

Force JAX onto a virtual 8-device CPU mesh *before* jax is used anywhere,
so multi-chip sharding paths (shard_map islands, psum/ppermute migration)
are exercised without TPU hardware. Bench and production paths do NOT do
this: they run on JAX's default backend — the real chip where there is
one (``chip_smoke.py`` is the command that proves it).
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _obs_switch_put_back():
    """The observability switch is the process's (obs/metrics.py): a
    test that turns it off must not decide what the next file on the
    same worker records."""
    from namazu_tpu.obs import metrics

    was_on = metrics.enabled()
    yield
    metrics.configure(was_on)

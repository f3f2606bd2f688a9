import os

# Tests run on a virtual 8-device CPU platform: sharding/collective tests
# need a mesh, and unit numerics want CPU float32.  The config update
# forces CPU whatever JAX_PLATFORMS says, so pytest never takes a chip.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from jax.extend.backend import clear_backends  # noqa: E402

clear_backends()  # no-op when nothing initialized yet

assert jax.default_backend() == "cpu", jax.default_backend()
assert len(jax.devices()) == 8, jax.devices()

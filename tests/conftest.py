"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the reference's test strategy of
"multi-node without a real cluster", SURVEY.md §4/§5): sharding and collective
logic is validated without TPU hardware, exactly like the driver's
``dryrun_multichip`` check.  Must be set before jax is imported anywhere.
"""

import os

# Tests run on the CPU: the virtual 8-device mesh below stands in for a
# chip host, and a test must never need (or hold) a real accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402  (env above must be set first)

# A plugin may have imported jax before this file ran; the live config
# wins over the snapshot it took of the environment.
jax.config.update("jax_platforms", "cpu")
# Persistent compilation cache (JAX_COMPILATION_CACHE_DIR if set, else
# <repo>/.jax_cache): the pairing kernels take tens of seconds to compile.
# Same call the node startup path makes.
from lighthouse_tpu.ops.compile_cache import configure_persistent_cache  # noqa: E402

configure_persistent_cache()


def pytest_configure(config):
    # tier-1 runs with -m 'not slow'; register the marker so pytest does not
    # warn on the opt-in big-bucket executions.
    config.addinivalue_line("markers", "slow: excluded from the tier-1 gate")

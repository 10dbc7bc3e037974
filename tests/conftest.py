"""Test configuration: run the suite on a virtual 8-device CPU platform.

Multi-chip sharding is tested on a virtual CPU mesh; the driver dry-runs the
real multi-chip path separately via __graft_entry__.dryrun_multichip, and
VENEUR_TPU_TEST_REAL=1 runs this suite against real devices instead.

JAX backends initialize lazily, so overriding the platform through
jax.config before any backend is touched works even where jax is already
imported. XLA_FLAGS is read at backend init, so setting it here (before the
first jax computation) is early enough.
"""

import os

# Skip the startup flush-program warmup in CLI subprocess tests (env
# overlay reaches them through load_config): each fresh process would
# otherwise pay the full XLA compile, blowing restart-test deadlines on
# a loaded single-core runner. In-process test servers share the jit
# cache, so warmup is nearly free there and stays on.
os.environ.setdefault("VENEUR_TPU_WARMUP_COMPILE", "false")

if not os.environ.get("VENEUR_TPU_TEST_REAL"):
    _want = "--xla_force_host_platform_device_count=8"
    flags = os.environ.get("XLA_FLAGS", "")
    if _want not in flags:
        os.environ["XLA_FLAGS"] = (_want + " " + flags).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")

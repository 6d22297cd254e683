import os

import jax

# Smoke tests and benches must see the real (single) CPU device; only the
# dry-run sets xla_force_host_platform_device_count (and only in its own
# process).
assert "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", "")

# No persistent compilation cache under test: a test run must not fill the
# checkout's .jax_cache/ (run_sweep enables it by default).  The variable
# carries the setting into the subprocesses some tests start.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
jax.config.update("jax_enable_compilation_cache", False)

# Hypothesis profiles: CI runs the differential/property harness with a
# fixed, derandomized profile (HYPOTHESIS_PROFILE=ci) so the kernel-parity
# gate is reproducible run-to-run; locally the default profile keeps the
# suite fast.  Tests that set @settings(...) explicitly keep their own
# example counts.
try:
    from hypothesis import settings as _hyp_settings

    _hyp_settings.register_profile("ci", derandomize=True, max_examples=50,
                                   deadline=None)
    _hyp_settings.register_profile("dev", max_examples=20, deadline=None)
    _hyp_settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "dev"))
except ImportError:  # pragma: no cover - hypothesis-less environments
    pass

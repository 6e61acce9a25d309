import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (the dry-run sets 512 in its own process).

# Persistent XLA compilation cache: the suite is compile-bound on CPU, and
# test programs are identical run-to-run, so warm tier-1 reruns skip most
# XLA work. Must be configured before the first jax computation.
import jax  # noqa: E402

from repro.launch import compile_cache  # noqa: E402

compile_cache.configure()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

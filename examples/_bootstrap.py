"""Shared example bootstrap (import before jax in an example's header).

Importing this module puts the repo root on sys.path (the examples run
as plain scripts, unpip-installed) and provides the one flag that must
act BEFORE the first JAX backend use:

    --force-cpu-devices N   run on N emulated CPU devices

It pins the platform through jax.config before backend init — the same
bootstrap tests/conftest.py uses; `JAX_PLATFORMS=cpu` in the
environment is equivalent.  The flag is left in sys.argv so the
example's argparse can document and record it.

Without the flag the example runs on whatever JAX finds, with the
persistent compile cache on (apex_tpu.utils.compile_cache); emulated
CPU devices compile in seconds and keep test runs off shared disk
state, so they go without.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _flag_value():
    for i, a in enumerate(sys.argv):
        if a == "--force-cpu-devices":
            if i + 1 >= len(sys.argv):
                sys.exit("--force-cpu-devices requires an integer value")
            return sys.argv[i + 1]
        if a.startswith("--force-cpu-devices="):
            return a.split("=", 1)[1]
    return None


def force_cpu_devices_from_argv():
    """Read --force-cpu-devices N (or =N) from sys.argv and act on it;
    absent or 0 leaves the platform to JAX and turns the compile cache
    on.  The flag is deliberately LEFT in sys.argv (module docstring)
    so the example's argparse can document and record it."""
    raw = _flag_value()
    try:
        n = 0 if raw is None else int(raw)
    except ValueError:
        sys.exit(f"--force-cpu-devices requires an integer value, "
                 f"got {raw!r}")
    if n <= 0:
        from apex_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        return
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)

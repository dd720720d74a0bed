"""Suite-wide environment, set before any test module imports jax.

The multi-device tests build meshes of up to 8 devices in-process.  On a
CPU-only machine XLA provides them only when the flag is set before the
first jax import, so the suite sets it here rather than relying on the
command that launched pytest.  A caller that sets ``XLA_FLAGS`` itself
keeps its own value.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

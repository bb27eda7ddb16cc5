"""Four virtual CPU devices, for the tests of the reference split over
several chips; set before JAX makes its CPU backend."""
import os

_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {_FLAG}=4"

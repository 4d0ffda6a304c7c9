"""The data-parallel kind's tests need more than one device: four virtual CPU
devices, asked for before any test touches JAX (the other tests here run on
the first of them as before).  Run beside ``tests/``, whose own conftest has
already made eight, the request comes too late and is not needed."""

import jax

try:
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 4)
except RuntimeError:  # the backend is up: tests/conftest.py made it, with 8 devices
    pass

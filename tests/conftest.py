import os
import sys

# Tests run single-device (the dry-run is its own process with 512 fake
# devices — do NOT set xla_force_host_platform_device_count here).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Entry points under test place JAX's persistent compile cache in the
# checkout (repro.launch.compile_cache); tests write nothing there.
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def tiny(cfg, **kw):
    """Shrink a reduced config further for fast tests."""
    base = dict(d_model=64, vocab_size=128, d_ff=128 if cfg.d_ff else 0)
    base.update(kw)
    return cfg.reduced(**base)

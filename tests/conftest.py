import os
import sys

# Tests run on the single real CPU device (the dry-run sets its own flags
# in a separate process). Keep XLA quiet and single-threaded-friendly.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from hypothesis import settings  # noqa: E402

from repro.configs import get_arch, reduced  # noqa: E402
from repro.core import runtime  # noqa: E402
from repro.models import transformer  # noqa: E402

# Property tests draw the same examples on every run and keep no example
# database on disk.
settings.register_profile("repro", derandomize=True, database=None)
settings.load_profile("repro")


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_executables():
    """The suite compiles hundreds of distinct executables (engine
    lanes x strategies x backends x run/run_compiled); keeping them
    all live eventually segfaults XLA's CPU compiler deep into the
    run.  No test shares jitted state across modules, so drop the
    caches at module boundaries (via the one shared dropper in
    repro.core.runtime — same valve bench_serving.py uses)."""
    yield
    runtime.drop_executables()


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def tiny_cfg():
    return reduced(get_arch("internlm2-1.8b"), n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                   vocab_size=128)


@pytest.fixture(scope="session")
def tiny_params(tiny_cfg, rng_key):
    return transformer.init_params(tiny_cfg, rng_key)


def make_tokens(key, cfg, batch=2, n=32):
    return jax.random.randint(key, (batch, n), 0, cfg.vocab_size - 1)

# Shared fixtures. NOTE: no XLA_FLAGS here — tests must see the real single
# CPU device; multi-device tests spawn subprocesses (test_distributed.py).

import numpy as np
import pytest

import jax

# Tests drive the entry points in-process, and those place JAX's persistent
# compilation cache (repro.core.engine.enable_compile_cache). Keep that
# cache off here: the xdist workers would share one directory, and its
# writes are not atomic.
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def key():
    return jax.random.key(0)

"""Shared fixtures. NOTE: no XLA_FLAGS here — unit tests run on 1 CPU
device by design; multi-device tests spawn subprocesses with their own
--xla_force_host_platform_device_count (see test_distributed.py).

Two hypothesis settings profiles are registered (the property tests
themselves never pin max_examples, so the profile is in charge):
  - "dev" (default): few examples, no deadline — fast local iteration.
  - "ci": more examples, derandomized (fixed seed) so CI runs are
    reproducible and actually exercise the properties. Selected via
    HYPOTHESIS_PROFILE=ci (set by .github/workflows/ci.yml).
"""
import os

import hypothesis
import jax
import pytest

hypothesis.settings.register_profile("dev", deadline=None, max_examples=10)
hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=50, derandomize=True, print_blob=True)
hypothesis.settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "dev"))


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _release_compiled_programs():
    """Drop JAX's compiled-program caches after every test.

    Each XLA:CPU executable holds memory mappings until it is dropped,
    and one worker process runs many test files: the property tests
    alone compile hundreds of shapes, enough to reach the kernel's
    per-process map limit (vm.max_map_count), where the next compile
    segfaults."""
    yield
    jax.clear_caches()

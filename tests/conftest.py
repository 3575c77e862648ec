"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests must see the
real single CPU device; only launch/dryrun.py forces 512 host devices.

The persistent compilation cache is off for the test run: the entry
points point it into the checkout (launch/compile_cache.py), and a test
run must leave nothing there.
"""
import jax
import pytest

jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session", autouse=True)
def _x64_off():
    jax.config.update("jax_enable_x64", False)
    yield


def seeds(n):
    return [jax.random.PRNGKey(i) for i in range(n)]

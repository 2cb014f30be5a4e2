"""The CPU tests of the benchmark keep JAX's persistent compilation cache
off: a test process must not write into the checkout or change the cache of
the tests that share its worker."""
import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    import run
    monkeypatch.setattr(run, "enable_compile_cache", lambda out_dir: None)

"""Shared test harness hooks.

``jax.clear_caches()`` between tests/modules: on the CPU backend a long
pytest process accumulates every compiled executable of every test
(hundreds of XLA:CPU JIT programs); past a threshold the next
``backend_compile`` segfaults inside LLVM — or, worse, silently
miscompiles (observed as deterministic-looking garbage logits late in a
heavily-compiling process, with the same stack as the crash). Dropping the
caches bounds live JIT code. Cross-module reuse is ~nil (modules don't
share shapes or configs) so the module-boundary clear is free; the
conformance matrix additionally clears per-test because its 24 cells each
compile a distinct config and the corruption was observed *inside* that
module.
"""

import jax
import pytest

# modules whose per-test compile churn is large enough to hit the XLA:CPU
# JIT corruption on their own (each test uses a fresh config, so per-test
# clearing costs no recompiles)
_CLEAR_EVERY_TEST = {"test_serving_conformance", "test_deepseek_v2"}


@pytest.fixture(autouse=True, scope="module")
def _bound_jit_code_memory():
    yield
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _bound_jit_code_memory_per_test(request):
    yield
    if request.node.module.__name__ in _CLEAR_EVERY_TEST:
        jax.clear_caches()

"""The system under test, as the benchmark reaches it: the program's
configuration for a cell, the key the seed gives, and a check that the
weights the benchmark makes have the program's own layout."""

from __future__ import annotations

import bench


def program_config(cell: bench.Cell, overrides: dict):
    """The registry's config of the cell's arch with the config file's
    overrides (and a test's)."""
    from repro.configs import get_config
    conf = cell.config
    return get_config(conf["arch"], **{**conf["overrides"], **overrides})


def seed_key(seed: int):
    """A PRNG key from a seed of any size."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def check_layout(cfg, params_shape) -> None:
    """The benchmark's weights must have the program's own layout."""
    import jax
    from repro.models import model as MD
    want = jax.eval_shape(lambda: MD.init_params(jax.random.PRNGKey(0), cfg))
    got_s, want_s = (jax.tree_util.tree_structure(t) for t in (params_shape, want))
    if got_s != want_s:
        raise ValueError(f"weights tree {got_s} differs from the program's {want_s}")
    for a, b in zip(jax.tree_util.tree_leaves(params_shape),
                    jax.tree_util.tree_leaves(want)):
        if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
            raise ValueError(f"leaf {a.shape} {a.dtype} vs program {b.shape} {b.dtype}")

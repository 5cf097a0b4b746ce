"""The comparisons that decide ``correct``."""
from __future__ import annotations

import numpy as np


def same_structure(params, cfg) -> None:
    """Raise unless ``params`` (the benchmark's weights) has the leaf
    names, shapes and dtypes of the program's model ``cfg``."""
    import jax

    from repro.models.registry import init_for
    want = jax.eval_shape(lambda: init_for(jax.random.PRNGKey(0), cfg))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    if want != got:
        raise RuntimeError(f"benchmark weights {got} do not match the "
                           f"program's model {want}")


def _norms(base: dict, tree: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(tree[k], np.float64)
                                    - np.asarray(base[k], np.float64)))
            for k in base}


def norm_gap(base: dict, prog: dict, ref: dict) -> float:
    """Worst leaf's gap between the norm of the program's change from
    ``base`` and the reference's, over the larger of the reference's norm
    of that leaf and of the median leaf. Leaves whose reference change is
    under a thousandth of the median leaf's are left out."""
    r, p = _norms(base, ref), _norms(base, prog)
    med = float(np.median(list(r.values())))
    return max(abs(p[k] - r[k]) / max(r[k], med)
               for k in r if r[k] >= 1e-3 * med)


def median_diff(base: dict, prog: dict, ref: dict) -> float:
    """The median leaf's norm of the difference between the program's
    change from ``base`` and the reference's, over the larger of the
    reference's norm of that leaf and of the median leaf (the leaves of
    ``norm_gap``). A lower precision moves every leaf off the reference
    while the norms of the changes stay alike; rounding amplified over
    many steps moves the worst leaf as far now and then, the median leaf
    less (PERF.md)."""
    r, d = _norms(base, ref), _norms(ref, prog)
    med = float(np.median(list(r.values())))
    return float(np.median([d[k] / max(r[k], med)
                            for k in r if r[k] >= 1e-3 * med]))

"""Property tests for the sharding-spec layer (hypothesis)."""
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from jax.sharding import PartitionSpec as P

from repro.models.common import sanitize_dim

AXES = {"data": 16, "model": 16, "pod": 2}


@given(
    st.integers(1, 1 << 20),
    st.lists(st.sampled_from(["data", "model", "pod"]), max_size=3,
             unique=True),
)
@settings(max_examples=200, deadline=None)
def test_sanitize_dim_divisibility(dim, axes):
    """Whatever sanitize_dim keeps must divide the dimension."""
    kept = sanitize_dim(tuple(axes) if axes else None, dim, AXES)
    if kept is None:
        return
    names = (kept,) if isinstance(kept, str) else kept
    total = int(np.prod([AXES[a] for a in names]))
    assert dim % total == 0
    # kept axes are a prefix-respecting subset of the requested ones
    assert all(a in axes for a in names)


@given(st.integers(1, 4096))
@settings(max_examples=100, deadline=None)
def test_sanitize_dim_greedy_prefix(dim):
    """Axes are consumed greedily in order: if the first axis doesn't
    divide, later ones may still apply only if divisibility holds with the
    accumulated product."""
    kept = sanitize_dim(("data", "model"), dim, AXES)
    if dim % 16:
        assert kept is None or "data" not in (
            (kept,) if isinstance(kept, str) else kept
        )
    if dim % 256 == 0:
        assert kept == ("data", "model")


def test_param_specs_cover_every_leaf_rank():
    """Every spec has exactly the rank of its leaf (P padding contract)."""
    import jax

    from repro.configs import ASSIGNED_ARCHS, get_config
    from repro.launch.mesh import make_abstract_mesh
    from repro.launch.sharding import param_specs
    from repro.models import transformer as T

    mesh = make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    for arch in ASSIGNED_ARCHS[:4]:
        cfg = get_config(arch)
        shapes = jax.eval_shape(
            lambda cfg=cfg: T.init_params(cfg, jax.random.key(0))
        )
        specs = param_specs(mesh, shapes)
        for leaf, spec in zip(
            jax.tree.leaves(shapes),
            jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P)),
        ):
            assert len(spec) <= leaf.ndim

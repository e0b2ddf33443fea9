"""Distribution layer beyond the allocator: the ambient mesh, rule
overrides, tree_shardings and the ambient-mesh constrain helper.

(The allocator semantics themselves are pinned by ``test_sharding.py``.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec as P

from repro.dist.sharding import (AxisRule, AxisRules, RULES_SERVE,
                                 RULES_TRAIN, ambient_mesh, constrain,
                                 logical_to_spec, sanitize_spec,
                                 tree_shardings)
from repro.launch.mesh import make_host_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


@pytest.fixture(scope="module")
def abstract():
    return AbstractMesh((16, 16), ("data", "model"))


# ---------------------------------------------------------------------------
# ambient mesh / host mesh
# ---------------------------------------------------------------------------


def test_get_abstract_mesh_none_outside_context():
    assert ambient_mesh() is None


def test_get_abstract_mesh_sees_ambient_mesh(mesh):
    with jax.set_mesh(mesh):
        m = ambient_mesh()
        assert m is not None
        assert tuple(m.axis_names) == ("data", "model")
        assert dict(m.shape) == dict(mesh.shape)
    assert ambient_mesh() is None


def test_host_mesh_refuses_more_devices_than_exist():
    """An unbuildable mesh is an error, never a silently smaller mesh."""
    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        make_host_mesh(data=n + 1)
    with pytest.raises(ValueError, match="devices"):
        make_host_mesh(data=n, model=2)


def test_host_mesh_takes_the_first_devices():
    m = make_host_mesh(data=1, model=1)
    assert dict(m.shape) == {"data": 1, "model": 1}
    assert list(m.devices.flat) == jax.devices()[:1]


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------


def test_override_rebinds_axes_keeps_priority(abstract):
    rules = RULES_SERVE.override(kv_seq=("data", "model"))
    assert rules.rule("kv_seq").axes == ("data", "model")
    assert rules.rule("kv_seq").priority == RULES_SERVE.rule("kv_seq").priority
    # the original table is untouched
    assert RULES_SERVE.rule("kv_seq").axes == ("model",)
    spec = logical_to_spec(("batch", "kv_seq", "kv_heads", "head_dim"), rules,
                           shape=(1, 32768, 8, 64), mesh=abstract)
    assert spec == P(None, ("data", "model"))


def test_override_unknown_name_gets_default_priority(abstract):
    rules = RULES_SERVE.override(novel=("model",))
    assert rules.rule("novel") == AxisRule(("model",),
                                           rules.rule("novel").priority)
    spec = logical_to_spec(("novel",), rules, shape=(64,), mesh=abstract)
    assert spec == P("model")


def test_unknown_and_none_names_replicate(abstract):
    spec = logical_to_spec((None, "not_a_rule", "heads"), RULES_SERVE,
                           shape=(8, 8, 32), mesh=abstract)
    assert spec == P(None, None, "model")


def test_rank_mismatch_raises(abstract):
    with pytest.raises(ValueError, match="rank mismatch"):
        logical_to_spec(("batch",), RULES_SERVE, shape=(8, 8), mesh=abstract)


def test_train_fsdp_on_expert_weights(abstract):
    """MoE expert weights in train: EP over model, FSDP over data."""
    spec = logical_to_spec(("experts", "expert_embed", "mlp"), RULES_TRAIN,
                           shape=(64, 2048, 1408), mesh=abstract)
    assert spec == P("model", "data")


# ---------------------------------------------------------------------------
# sanitize_spec
# ---------------------------------------------------------------------------


def test_sanitize_drops_unknown_axis(abstract):
    assert sanitize_spec((64, 64), P("expert", "model"), abstract) \
        == P(None, "model")


def test_sanitize_drops_indivisible(abstract):
    assert sanitize_spec((30, 64), P("data", "model"), abstract) \
        == P(None, "model")


def test_sanitize_partial_axis_group(abstract):
    # 32 divides data(16) joined with... model would need 256: keep data only
    assert sanitize_spec((32,), P(("data", "model"),), abstract) == P("data")


def test_sanitize_idempotent_on_allocator_output(abstract):
    spec = logical_to_spec(("batch", "kv_seq", "kv_heads", "head_dim"),
                           RULES_SERVE, shape=(128, 32768, 16, 64),
                           mesh=abstract)
    assert sanitize_spec((128, 32768, 16, 64), spec, abstract) == spec


# ---------------------------------------------------------------------------
# tree_shardings / constrain
# ---------------------------------------------------------------------------


def test_tree_shardings_matches_spec_tree(mesh):
    from repro.configs import get_smoke_config
    from repro.models import layers as L
    from repro.models import transformer as T

    cfg = get_smoke_config("llama3.2-1b")
    specs = T.init_model(cfg, L.SpecMaker(jnp.bfloat16))
    axes = T.init_model(cfg, L.AxesMaker())
    sh = tree_shardings(axes, specs, mesh, RULES_SERVE)
    assert jax.tree.structure(sh) == jax.tree.structure(specs)
    for leaf in jax.tree.leaves(sh):
        assert isinstance(leaf, NamedSharding)
        assert leaf.mesh is mesh
    # spot-check: stacked attention q-projection (layers, embed, heads,
    # head_dim) is TP over heads, replicated elsewhere
    wq = sh["segments"][0][0]["attn"]["wq"]
    assert wq.spec == P(None, None, "model")


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 8))
    assert constrain(x, ("batch", "seq"), RULES_SERVE) is x
    assert constrain(x, ("batch", "seq"), None) is x


def test_constrain_under_mesh_preserves_values(mesh):
    x = jnp.arange(8.0).reshape(2, 4)

    @jax.jit
    def f(x):
        return constrain(x, ("batch", "seq"), RULES_TRAIN) * 2

    with jax.set_mesh(mesh):
        np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x) * 2)

"""Planner subsystem tests: ShardingPlan validity on all meshes, plan-level
validation failures, and mesh construction (Auto axis types)."""
import jax
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro import configs as C
from repro.parallel import meshes, planner

MESHES = [
    ((16, 16), ("data", "model")),
    ((2, 16, 16), ("pod", "data", "model")),
]


def _leaf_shardings(plan):
    out = list(jax.tree_util.tree_leaves(plan.param_shardings()))
    if plan.data is not None:
        out += list(jax.tree_util.tree_leaves(plan.data_shardings()))
    if plan.cache is not None:
        out += list(jax.tree_util.tree_leaves(plan.cache_shardings()))
    return out


@pytest.mark.parametrize("sizes,names", MESHES, ids=["single_pod", "multi_pod"])
@pytest.mark.parametrize("arch", C.ARCH_IDS)
def test_plan_accepted_by_namedsharding_on_production_meshes(arch, sizes, names):
    """Every spec a plan emits must be constructible as a NamedSharding on
    the abstract production meshes (NamedSharding validates axes)."""
    mesh = meshes.make_abstract_mesh(sizes, names)
    cfg = C.get_config(arch)
    plan = planner.plan_for(cfg, mesh, shape=C.DECODE_32K)
    shardings = _leaf_shardings(plan)
    assert shardings and all(isinstance(s, NamedSharding) for s in shardings)


@pytest.mark.parametrize("arch", C.ARCH_IDS)
def test_plan_degrades_to_replication_on_host_mesh(arch):
    """On the 1-device CPU mesh every leaf must be effectively replicated:
    any axes the rules assign have total size 1."""
    mesh = meshes.make_host_mesh()
    mesh_shape = meshes.shape_dict(mesh)
    cfg = C.get_config(arch)
    plan = planner.plan_for(cfg, mesh, shape=C.DECODE_32K)
    assert all(isinstance(s, NamedSharding) for s in _leaf_shardings(plan))
    for rep in plan.report:
        for d in rep.dims:
            n = 1
            for a in d.axes:
                n *= mesh_shape[a]
            assert n == 1, (rep.path, d)


def test_pooled_serving_plan_keyed_by_slot_count():
    """plan_for(pool_slots=) plans the slot-pooled cache tree: structure
    matches registry.init_pool_cache (paged since PR 6 — one span-sized
    page per slot by default), slot leaves ride the data axis when it
    divides (docs/DESIGN_scaling.md), ``pos`` stays replicated (gather
    metadata every shard consults), and the production mesh validates."""
    from repro.models import registry

    cfg = C.smoke_config("llama3-8b")
    shape = C.ShapeConfig("serve", 32, 8, "decode")

    # host mesh: the 1-wide data axis trivially divides everything, so
    # the slot leaves carry the axis (a physical no-op on one device) and
    # the default page count needs no rounding
    plan = planner.plan_for(cfg, meshes.make_host_mesh(), shape=shape,
                            pool_slots=8)
    assert plan.pool_slots == 8
    assert plan.page_size == 32 and plan.num_pages == 8
    assert plan.data_shards == 1
    pooled = jax.eval_shape(lambda: registry.init_pool_cache(cfg, 8, 32))
    assert (jax.tree_util.tree_structure(pooled)
            == jax.tree_util.tree_structure(plan.cache))
    # physical page store: 8 pages + the null page, per-slot tables
    assert plan.cache_abstract["pos"].shape == (9, 32)
    assert plan.cache_abstract["len"].shape == (8,)
    assert plan.cache_abstract["table"].shape == (8, 1)
    assert plan.cache["pos"] == P()
    assert plan.cache["len"] == P("data")
    assert plan.cache["table"] == P("data")

    # production mesh: 8 slots are ragged over the 16-wide data axis, so
    # the slot leaves fall back to replication, while the defaulted page
    # count rounds up so the page-store axis (num_pages + 1) divides
    plan = planner.plan_for(
        cfg, meshes.make_abstract_mesh((16, 16), ("data", "model")),
        shape=shape, pool_slots=8,
    )
    assert plan.page_size == 32 and plan.num_pages == 15  # 15 + 1 = 16
    assert plan.data_shards == 16 and plan.model_shards == 16
    assert plan.cache_abstract["pos"].shape == (16, 32)
    assert plan.cache["pos"] == P()
    assert plan.cache["len"] == P() and plan.cache["table"] == P()

    with pytest.raises(planner.ShardingPlanError, match="pool_slots"):
        planner.plan_for(cfg, meshes.make_host_mesh(), shape=shape,
                         pool_slots=4)


def test_sharded_pool_plan_keyed_by_mesh_shape():
    """The sharded-pool layout (docs/DESIGN_scaling.md): on a mesh whose
    data axis divides the slot count, slots / page tables / page stores /
    beta leaves shard over 'data', weights keep their 'model' shards, and
    the mesh shape is recorded as a plan key next to the page geometry."""
    from repro.core.policy import KV_PINNED

    cfg = C.smoke_config("llama3-8b")
    shape = C.ShapeConfig("serve", 32, 8, "decode")
    mesh = meshes.make_abstract_mesh((2, 2), ("data", "model"))
    plan = planner.plan_for(cfg, mesh, shape=shape, pool_slots=8,
                            page_size=4, kv_quant=KV_PINNED)
    assert plan.data_shards == 2 and plan.model_shards == 2
    assert plan.mesh_shape() == {"data": 2, "model": 2}
    # default pages = 8 slots * 8 pages/slot = 64; 64 + 1 rounds to 66 so
    # the page-store axis splits in two
    assert plan.page_size == 4 and plan.num_pages == 65
    assert plan.kv_bits == KV_PINNED.bits
    # slot axis -> data; physical-page axis -> data (k/v and betas alike)
    assert plan.cache["len"] == P("data")
    assert plan.cache["table"] == P("data")
    assert tuple(plan.cache["k"])[1] == "data"
    assert tuple(plan.cache["k_beta"])[1] == "data"
    assert tuple(plan.cache["v_beta"])[1] == "data"
    assert plan.cache["pos"] == P()
    # an explicit num_pages is honoured verbatim (no silent rounding)
    plan2 = planner.plan_for(cfg, mesh, shape=shape, pool_slots=8,
                             page_size=4, num_pages=64)
    assert plan2.num_pages == 64
    # and the multi-pod mesh composes ('pod', 'data') on the slot axis
    plan3 = planner.plan_for(
        cfg, meshes.make_abstract_mesh((2, 2, 16), ("pod", "data", "model")),
        shape=shape, pool_slots=8,
    )
    assert plan3.data_shards == 4
    assert plan3.cache["len"] == P(("pod", "data"))


def test_pooled_serving_plan_keyed_by_page_geometry():
    """Small pages re-key the cache plan: the k/v leaves become
    (num_pages+1)-page physical stores and the resolved geometry is
    recorded so PoolEngine can refuse a mismatched plan."""
    from repro.models import registry

    cfg = C.smoke_config("llama3-8b")
    shape = C.ShapeConfig("serve", 32, 8, "decode")
    plan = planner.plan_for(
        cfg, meshes.make_host_mesh(), shape=shape, pool_slots=8, page_size=4
    )
    assert plan.page_size == 4 and plan.num_pages == 64
    assert plan.cache_abstract["pos"].shape == (65, 4)
    assert plan.cache_abstract["table"].shape == (8, 8)
    pooled = jax.eval_shape(
        lambda: registry.init_pool_cache(cfg, 8, 32, page_size=4)
    )
    assert (jax.tree_util.tree_structure(pooled)
            == jax.tree_util.tree_structure(plan.cache))
    assert all(isinstance(s, NamedSharding) for s in _leaf_shardings(plan))


def test_plan_moe_decisions():
    """llama4 (16e) -> EP over the 16-way model axis; grok (8e) -> TP
    inside each expert (8 does not divide 16)."""
    mesh = meshes.make_production_mesh(abstract=True)
    l4 = planner.plan_for(C.get_config("llama4-scout-17b-a16e"), mesh)
    gk = planner.plan_for(C.get_config("grok-1-314b"), mesh)
    assert l4.moe and set(l4.moe.values()) == {"EP"}
    assert gk.moe and set(gk.moe.values()) == {"TP"}


def test_validation_rejects_nondivisible_and_axis_reuse():
    mesh = meshes.make_production_mesh(abstract=True)  # (16, 16)
    good = planner.plan_for(C.get_config("olmo-1b"), mesh)

    def plan_with(shape, spec):
        rep = planner._analyze_leaf("param", "bogus", shape, spec)
        return planner.ShardingPlan(
            mesh=mesh, params=None, data=None, cache=None, moe={},
            report=(rep,),
        )

    with pytest.raises(planner.ShardingPlanError, match="not divisible"):
        plan_with((24, 8), P("model", None)).validate()
    with pytest.raises(planner.ShardingPlanError, match="used twice"):
        plan_with((32, 32), P("model", "model")).validate()
    with pytest.raises(planner.ShardingPlanError, match="unknown mesh axis"):
        plan_with((32, 32), P("nonesuch", None)).validate()
    assert good.validate() is good  # idempotent on a valid plan


def test_plan_summary_mentions_every_leaf():
    mesh = meshes.make_production_mesh(abstract=True)
    plan = planner.plan_for(C.get_config("llama4-scout-17b-a16e"), mesh)
    text = plan.summary()
    assert "[param]" in text and "[moe]" in text
    assert len(text.splitlines()) >= len(plan.report)


# ---------------------------------------------------------------------------
# train/step.py consumes the ACTIVE plan (actshard.active_plan()) — the
# plan is the single sharding source end-to-end; no raw mesh= argument.
# ---------------------------------------------------------------------------


def test_split_micro_consumes_active_plan(monkeypatch):
    import inspect

    import jax.numpy as jnp

    from repro.parallel import actshard
    from repro.train import step as train_step_mod

    # the raw mesh= escape hatch is gone from the public factory
    assert "mesh" not in inspect.signature(
        train_step_mod.make_train_step
    ).parameters
    assert "mesh" not in inspect.signature(
        train_step_mod._split_micro
    ).parameters

    mesh = meshes.make_production_mesh(abstract=True)  # (16, 16)
    plan = planner.plan_for(C.get_config("olmo-1b"), mesh)
    batch = {"tokens": jnp.zeros((32, 8), jnp.int32)}

    seen = []

    def spy(x, sharding):
        seen.append(sharding)
        return x

    monkeypatch.setattr(jax.lax, "with_sharding_constraint", spy)

    # no active plan -> unconstrained reshape (CPU tests / single device)
    micros = train_step_mod._split_micro(batch, 2)
    assert micros["tokens"].shape == (2, 16, 8)
    assert seen == []

    # active plan -> the microbatch reshape is pinned with the PLAN's
    # activation rule (batch dim 1 -> fsdp axes, seq dim 2 -> model)
    with actshard.use_plan(plan):
        micros = train_step_mod._split_micro(batch, 2)
    assert micros["tokens"].shape == (2, 16, 8)
    assert len(seen) == 1
    (ns,) = seen
    assert isinstance(ns, NamedSharding) and ns.mesh is mesh
    assert ns.spec == plan.activation_pspec(
        3, batch_size=16, seq_len=8, batch_dim=1, seq_dim=2
    )
    # and that rule actually shards the batch dim on the production mesh
    assert tuple(ns.spec)[1] == "data"


# ---------------------------------------------------------------------------
# Mesh construction: the split (sizes, names) AbstractMesh call and Auto
# axis types on every mesh the module builds.
# ---------------------------------------------------------------------------


class _SplitStyleMesh:
    """AbstractMesh((size, ...), (name, ...), axis_types=...)."""

    def __init__(self, axis_sizes, axis_names=None, axis_types=None):
        if axis_names is None or not all(
            isinstance(s, int) for s in axis_sizes
        ):
            raise TypeError("expected (sizes, names)")
        self.axis_names = tuple(axis_names)
        self.axis_sizes = tuple(axis_sizes)
        self.axis_types = axis_types
        self.shape = dict(zip(self.axis_names, self.axis_sizes))


@pytest.mark.parametrize("fake", [_SplitStyleMesh], ids=["split_style"])
def test_shim_resolves_either_abstract_mesh_signature(monkeypatch, fake):
    monkeypatch.setattr(meshes, "AbstractMesh", fake)
    m = meshes.make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    assert meshes.axis_names(m) == ("pod", "data", "model")
    assert meshes.axis_sizes(m) == (2, 16, 16)
    assert meshes.shape_dict(m) == {"pod": 2, "data": 16, "model": 16}
    assert m.axis_types == (AxisType.Auto,) * 3


@pytest.mark.parametrize("build", ["concrete", "host", "serving", "abstract"])
def test_make_mesh_axis_types_are_auto(build):
    """Auto axes leave placement to NamedSharding pins + the partitioner;
    an Explicit mesh (jax.make_mesh's own default) makes the embedding
    gather demand an out_sharding and fails the train step."""
    m = {
        "concrete": lambda: meshes.make_mesh((1, 1), ("data", "model")),
        "host": meshes.make_host_mesh,
        "serving": meshes.make_serving_mesh,
        "abstract": lambda: meshes.make_abstract_mesh((2, 2), ("data", "model")),
    }[build]()
    assert tuple(m.axis_types) == (AxisType.Auto,) * len(m.axis_names)


def test_shim_builds_real_abstract_mesh_on_installed_jax():
    """Whatever signature this JAX ships, the shim must produce a usable
    AbstractMesh that NamedSharding accepts."""
    m = meshes.make_abstract_mesh((16, 16), ("data", "model"))
    assert meshes.shape_dict(m) == {"data": 16, "model": 16}
    ns = NamedSharding(m, P("data", "model"))
    assert ns.spec == P("data", "model")


def test_shim_rejects_mismatched_axes():
    with pytest.raises(ValueError):
        meshes.make_abstract_mesh((16, 16), ("data",))

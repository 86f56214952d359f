"""Sharding rules: every arch's param/cache PartitionSpecs must be valid and
structurally complete (validated on a degenerate 1×1 mesh — axis names are
what matter; divisibility is exercised by the 512-device dry-run)."""
import jax
import jax.numpy as jnp
import pytest
from _host_devices import run_on_host_devices
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES
from repro.configs.registry import ARCHS
from repro.distributed import sharding as shd
from repro.launch.input_specs import cache_specs
from repro.launch.mesh import make_host_mesh, make_test_mesh
from repro.models import Model
from repro.models import transformer as tfm


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


def _all_emitted_axes():
    """Union of every logical axis name any registry arch's params emit."""
    axes = set()
    for name in sorted(ARCHS):
        tree = Model(ARCHS[name]).param_axes()
        for leaf in jax.tree.leaves(
                tree, is_leaf=lambda x: isinstance(x, tuple) and all(
                    a is None or isinstance(a, str) for a in x)):
            axes.update(leaf)
    axes.discard(None)
    return axes


@pytest.mark.parametrize("phase", ["train", "decode", "serve"])
def test_rules_round_trip_every_emitted_axis(mesh, phase):
    """Every ParamDef logical axis any serving model emits must have an
    explicit entry in the rule set. ``_axes_to_spec`` silently replicates
    unmapped names (``rules.get(a, ())``), so a new layer introducing an
    axis the rules don't know would shard nothing and nobody would notice —
    this is the tripwire."""
    rules = shd.rules_for(mesh, phase)
    emitted = _all_emitted_axes()
    missing = sorted(a for a in emitted if a not in rules)
    assert not missing, (
        f"logical axes with no {phase!r} rule (would replicate silently): "
        f"{missing}")
    for a in emitted:                    # and every mapping must be physical
        phys = rules[a]
        for ax in ((phys,) if isinstance(phys, str) else phys):
            assert ax in ("pod", "data", "model"), (a, phys)


def test_serve_rules_never_split_a_contraction(mesh):
    """The bit-exactness invariant behind the serve layout: contraction-side
    weight axes (embed, the ``*_in`` family, MoE hidden) and the pre-down-
    projection activation gather keys must all be replicated, and no float
    reduction axis may map to a mesh axis."""
    rules = shd.rules_for(mesh, "serve")
    assert rules["phase"] == "serve" and rules["mesh"] is mesh
    for contraction_side in ("embed", "heads_in", "mlp_in", "rnn_in",
                             "moe_mlp", "moe_embed", "inner", "kv_seq",
                             "heads_act", "mlp_act", "rnn_act"):
        assert rules[contraction_side] == (), contraction_side
    # batch-like dims are the only sharded ones
    assert rules["batch"] == ("data",) and rules["cache_batch"] == ("data",)
    for batch_like in ("vocab", "heads", "kv_heads", "mlp", "experts",
                       "experts_run", "rnn"):
        assert rules[batch_like] == ("model",), batch_like
    # serve param specs stay valid (no duplicate mesh axes) for every arch
    for name in sorted(ARCHS):
        pspecs = shd.param_pspecs(Model(ARCHS[name]).param_axes(), rules)
        for ps in jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P)):
            NamedSharding(mesh, ps)


def test_serve_cache_pspecs_shard_rows_not_sequence(mesh):
    """Serve cache/pool specs: batch (slot/page/row) axis over "data", KV
    head and recurrent-channel dims over "model", never the sequence dim."""
    rules = shd.rules_for(mesh, "serve")
    for name in ("qwen2.5-3b", "recurrentgemma-9b"):
        pspecs = shd.cache_pspecs(ARCHS[name], rules)
        for ps in jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P)):
            NamedSharding(mesh, ps)
            assert "data" in ps                       # a row-sharded leaf
    qwen = shd.cache_pspecs(ARCHS["qwen2.5-3b"], rules)
    assert qwen["scan"]["sub0"]["k"] == P(None, "data", None, "model", None)
    rg = shd.cache_pspecs(ARCHS["recurrentgemma-9b"], rules)
    assert rg["scan"]["sub0"]["h"] == P(None, "data", "model")


def test_make_test_mesh_shapes():
    """make_test_mesh instantiates small explicit shapes (the production
    helper hard-codes pod slices no CPU host can build) and names axes
    rightmost-aligned; an oversized shape fails with a clear message."""
    m = make_test_mesh((1, 1))
    assert m.axis_names == ("data", "model")
    n = jax.device_count()
    if n >= 2:
        m2 = make_test_mesh((1, 2))
        assert dict(m2.shape) == {"data": 1, "model": 2}
    with pytest.raises(RuntimeError, match="device_count"):
        make_test_mesh((1024, 1024))


def test_shard_put_divisibility_fallback(mesh):
    """shard_put replicates (exactly) the dims a mesh axis cannot divide —
    device_put refuses uneven shardings, and a 2-KV-head config on a 4-way
    "model" axis must still serve, just unsharded on that dim."""
    x = jnp.arange(12.0).reshape(3, 4)
    out = shd.shard_put({"w": x}, {"w": P("data", "model")}, mesh)
    assert (out["w"] == x).all()
    spec = shd._divisible_spec((3, 4), P("data", "model"), mesh)
    assert spec == P(None, "model") or mesh.shape["data"] == 1


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_param_pspecs_valid(mesh, name):
    model = Model(ARCHS[name])
    rules = shd.rules_for(mesh, "train")
    pspecs = shd.param_pspecs(model.param_axes(), rules)
    specs = model.param_specs()
    flat_specs = jax.tree.leaves(specs)
    flat_ps = jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_specs) == len(flat_ps)
    for sds, ps in zip(flat_specs, flat_ps):
        assert isinstance(ps, P)
        assert len(ps) <= len(sds.shape)
        NamedSharding(mesh, ps)          # raises on duplicate/invalid axes


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_cache_pspecs_match_cache_structure(mesh, name):
    cfg = ARCHS[name]
    rules = shd.rules_for(mesh, "decode")
    pspecs = shd.cache_pspecs(cfg, rules)
    cspec = tfm.cache_spec(cfg, batch=2, capacity=64)
    s1 = jax.tree.structure(jax.tree.map(lambda _: 0, cspec))
    s2 = jax.tree.structure(jax.tree.map(lambda _: 0, pspecs,
                                         is_leaf=lambda x: isinstance(x, P)))
    assert s1 == s2
    for ps in jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P)):
        NamedSharding(mesh, ps)


def test_multi_pod_rules_add_pod_axis():
    mesh = make_host_mesh()
    rules_sp = shd.rules_for(mesh, "train")
    assert rules_sp["batch"] == ("data",)

    class FakeMesh:
        axis_names = ("pod", "data", "model")
    rules_mp = shd.rules_for(FakeMesh(), "train")
    assert rules_mp["batch"] == ("pod", "data")
    assert rules_mp["embed"] == ("pod", "data")


def test_constrain_noop_outside_rules_ctx():
    x = jnp.ones((4, 4))
    assert shd.constrain(x, "batch", None) is x


def test_dryrun_grid_covers_40_cells():
    from repro.configs.registry import all_cells
    cells = list(all_cells(include_skipped=True))
    assert len(cells) == 40
    active = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    assert len(active) == 33
    # every skip is a long_500k on a full-attention arch, with a reason
    for arch, shape, _, reason in skipped:
        assert shape.name == "long_500k"
        assert not arch.is_subquadratic
        assert reason


def test_weight_stationary_decode_rules():
    mesh = make_host_mesh()
    rules = shd.rules_for(mesh, "decode", weight_stationary=True)
    assert rules["batch"] == ()                      # activations replicated
    assert rules["cache_batch"] == ("data",)         # caches stay sharded
    assert rules["mlp"] == ("model",)                # weights stay 2D-sharded
    with pytest.raises(AssertionError):
        shd.rules_for(mesh, "train", weight_stationary=True)


def test_expert_parallel_rules():
    mesh = make_host_mesh()
    base = shd.rules_for(mesh, "train")
    ep = shd.rules_for(mesh, "train", expert_parallel=True)
    assert base["experts"] == () and base["moe_embed"] == ("data",)
    assert ep["experts"] == ("data",) and ep["moe_embed"] == ()
    assert ep["experts_run"] == ("data",) and ep["moe_tokens"] == ()
    # EP param specs stay valid (no duplicate axes) for the MoE archs
    for name in ("mixtral-8x22b", "dbrx-132b"):
        pspecs = shd.param_pspecs(Model(ARCHS[name]).param_axes(), ep)
        for ps in jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P)):
            NamedSharding(mesh, ps)


def test_seqpar_decode_attention_matches_ref(mesh):
    """shard_map flash-decode (LSE psum combine) == the naive oracle."""
    import jax.numpy as jnp
    from repro.distributed.collectives import make_seqpar_decode_attention
    from repro.kernels import ref
    fn = make_seqpar_decode_attention(mesh)
    B, W, K, G, hd = 2, 32, 2, 2, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, K * G, hd))
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, W, K, hd))
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, W, K, hd))
    for clen in (jnp.array(W - 1, jnp.int32), jnp.array([5, 20], jnp.int32)):
        with mesh:
            got = fn(q, kc, vc, clen, q_per_kv=G)
        want = ref.decode_attention(q, kc, vc, clen, q_per_kv=G)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5


_PER_SHARD = r'''
import functools
import jax, jax.numpy as jnp
from repro.distributed import sharding
from repro.kernels.decode_attention import decode_attention
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.launch.mesh import make_test_mesh

rules = sharding.rules_for(make_test_mesh((1, 4)), "serve")
B, G, hd, ps, P, nw = 4, 2, 16, 8, 24, 4
Q = ("batch", None, "kv_heads", None)
for K in (4, 2):           # KV heads that divide the 4-way axis, and not
    ks = jax.random.split(jax.random.PRNGKey(K), 4)
    q = jax.random.normal(ks[0], (B, 1, K * G, hd))
    kp = jax.random.normal(ks[1], (P, ps, K, hd))
    vp = jax.random.normal(ks[2], (P, ps, K, hd))
    bt = jax.random.randint(ks[3], (B, nw), 1, P)
    n = jnp.asarray([3, 9, 17, 30], jnp.int32)
    paged = functools.partial(paged_decode_attention, q_per_kv=G)
    pool = (None, None, "kv_heads", None)

    @jax.jit
    def sharded(q, kp, vp, bt, n):
        with sharding.use_rules(rules):
            return sharding.per_shard(paged, (q, kp, vp, bt, n),
                                      (Q, pool, pool, ("batch", None),
                                       ("batch",)), Q)
    assert bool(jnp.all(sharded(q, kp, vp, bt, n) == paged(q, kp, vp, bt, n)))
    dense = functools.partial(decode_attention, q_per_kv=G, block_w=16)
    kc = jax.random.normal(ks[1], (B, 32, K, hd))
    rows = ("batch", None, "kv_heads", None)

    @jax.jit
    def sharded_dense(q, kc, n):
        with sharding.use_rules(rules):
            return sharding.per_shard(dense, (q, kc, kc, n),
                                      (Q, rows, rows, ("batch",)), Q)
    assert bool(jnp.all(sharded_dense(q, kc, n) == dense(q, kc, kc, n)))
print("PER_SHARD_OK")
'''


def test_per_shard_kernels_match_the_plain_call(tmp_path):
    """Kernel calls run per device under a multi-device serve rule set and
    give the single-device values, whether or not the KV heads divide the
    mesh axis. Runs in a child with four host devices."""
    assert "PER_SHARD_OK" in run_on_host_devices(tmp_path, _PER_SHARD, 4)


_PER_SHARD_DATA = r'''
import functools
import jax, jax.numpy as jnp
from repro.configs.registry import ARCHS
from repro.distributed import sharding
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.launch.mesh import make_test_mesh
from repro.serving import kvpool

mesh = make_test_mesh((2, 4))
rules = sharding.rules_for(mesh, "serve")
cfg = ARCHS["qwen2.5-3b"].reduced(num_heads=8, num_kv_heads=4, head_dim=16)
B, G, ps, P, nw = 4, 2, 8, 24, 4
K, hd = cfg.num_kv_heads, cfg.head_dim
# the serving layout: pages over "data", KV heads over "model"
pool = kvpool.shard_rows(kvpool.init_paged_cache(cfg, P, ps), cfg, rules,
                         mesh)
kl, vl = jax.tree.leaves(pool)[:2]       # one layer group's [L, P, ps, K, hd]
ks = jax.random.split(jax.random.PRNGKey(0), 4)
kp = jax.device_put(jax.random.normal(ks[1], kl.shape, kl.dtype), kl.sharding)
vp = jax.device_put(jax.random.normal(ks[2], vl.shape, vl.dtype), vl.sharding)
assert kp.sharding.spec[1] == "data"
q = jax.random.normal(ks[0], (B, 1, K * G, hd), kp.dtype)
bt = jax.random.randint(ks[3], (B, nw), 1, P)
n = jnp.asarray([3, 9, 17, 30], jnp.int32)
paged = functools.partial(paged_decode_attention, q_per_kv=G)
Q = ("batch", None, "kv_heads", None)
pages = (None, None, "kv_heads", None)


@jax.jit
def sharded(q, kp, vp, bt, n):
    with sharding.use_rules(rules):
        return sharding.per_shard(paged, (q, kp[0], vp[0], bt, n),
                                  (Q, pages, pages, ("batch", None),
                                   ("batch",)), Q)


assert bool(jnp.all(sharded(q, kp, vp, bt, n) == paged(q, kp[0], vp[0], bt, n)))
hlo = sharded.lower(q, kp, vp, bt, n).compile().as_text()
assert "all-gather" in hlo
print("PER_SHARD_GATHERS_PAGES")
'''


def test_per_shard_paged_kernel_gathers_pages_over_data(tmp_path):
    """On a mesh with data > 1 the pool's pages are laid out over "data",
    but the paged decode kernel indexes the whole pool through block
    tables, so each device all-gathers the pages it does not hold before
    the kernel runs: the values still equal the plain call, at the cost of
    a whole-pool gather per layer and step. Runs in a child with eight host
    devices."""
    out = run_on_host_devices(tmp_path, _PER_SHARD_DATA, 8)
    assert "PER_SHARD_GATHERS_PAGES" in out

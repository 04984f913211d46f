"""Expert-parallel MoE — all-to-all dispatch matches a sequential
reference with identical routing/capacity semantics, differentiates, and
trains."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh


@pytest.fixture(scope="module")
def expert_mesh():
    return Mesh(np.asarray(jax.devices()), ("expert",))


def _expert_fn(p, x):
    return jnp.tanh(x @ p["w"]) @ p["v"]


def _make(rng, E, D, H):
    router_w = jnp.asarray(rng.normal(size=(D, E)), jnp.float32)
    params = {"w": jnp.asarray(rng.normal(size=(E, D, H)) * 0.4, jnp.float32),
              "v": jnp.asarray(rng.normal(size=(E, H, D)) * 0.4, jnp.float32)}
    return router_w, params


def _reference(router_w, params, x, E, capacity):
    """Same semantics, sequentially: tokens are routed per device-shard
    with per-(shard, expert) capacity."""
    T, D = x.shape
    local_t = T // E
    out = np.zeros_like(np.asarray(x))
    for d in range(E):
        xs = np.asarray(x[d * local_t:(d + 1) * local_t])
        logits = xs @ np.asarray(router_w)
        eid = logits.argmax(-1)
        gate = jax.nn.softmax(jnp.asarray(logits), axis=-1)
        counts = {}
        for i in range(local_t):
            j = int(eid[i])
            pos = counts.get(j, 0)
            counts[j] = pos + 1
            if pos >= capacity:
                continue  # dropped
            p_j = {k: np.asarray(v[j]) for k, v in params.items()}
            y = np.asarray(_expert_fn(
                {k: jnp.asarray(v) for k, v in p_j.items()},
                jnp.asarray(xs[i][None])))[0]
            out[d * local_t + i] = y * float(gate[i, j])
    return out


def test_moe_matches_reference(expert_mesh):
    from msrflute_tpu.ops.moe import moe_apply
    rng = np.random.default_rng(0)
    E = expert_mesh.shape["expert"]
    D, H, local_t = 6, 10, 8
    router_w, params = _make(rng, E, D, H)
    x = jnp.asarray(rng.normal(size=(E * local_t, D)), jnp.float32)
    cf = 2.0
    capacity = max(1, int(cf * local_t / E))
    out = moe_apply(router_w, params, _expert_fn, x, expert_mesh,
                    capacity_factor=cf)
    ref = _reference(router_w, params, x, E, capacity)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_moe_differentiates_and_trains(expert_mesh):
    from msrflute_tpu.ops.moe import moe_apply
    rng = np.random.default_rng(1)
    E = expert_mesh.shape["expert"]
    D, H, local_t = 4, 8, 8
    router_w, params = _make(rng, E, D, H)
    x = jnp.asarray(rng.normal(size=(E * local_t, D)), jnp.float32)
    teacher_rw, teacher_p = _make(np.random.default_rng(9), E, D, H)
    target = moe_apply(teacher_rw, teacher_p, _expert_fn, x, expert_mesh)

    @jax.jit
    def step(rw, p):
        def loss(rw, p):
            y = x + moe_apply(rw, p, _expert_fn, x, expert_mesh)
            return jnp.mean((y - (x + target)) ** 2)
        l, (g_rw, g_p) = jax.value_and_grad(loss, argnums=(0, 1))(rw, p)
        return (rw - 0.1 * g_rw,
                jax.tree.map(lambda w, g: w - 0.1 * g, p, g_p), l)

    losses = []
    for _ in range(30):
        router_w, params, l = step(router_w, params)
        losses.append(float(l))
    assert np.isfinite(losses).all()
    assert losses[-1] < 0.8 * losses[0], losses[::6]


def test_moe_rejects_bad_shapes(expert_mesh):
    from msrflute_tpu.ops.moe import moe_apply
    E = expert_mesh.shape["expert"]
    router_w = jnp.zeros((4, E))
    params = {"w": jnp.zeros((E + 1, 4, 4)), "v": jnp.zeros((E + 1, 4, 4))}
    with pytest.raises(ValueError, match="leading axis"):
        moe_apply(router_w, params, _expert_fn, jnp.zeros((E * 2, 4)),
                  expert_mesh)
    with pytest.raises(ValueError, match="not divisible"):
        moe_apply(router_w, {"w": jnp.zeros((E, 4, 4)),
                             "v": jnp.zeros((E, 4, 4))},
                  _expert_fn, jnp.zeros((E * 2 + 1, 4)), expert_mesh)


def test_moeffn_local_matches_ep(expert_mesh):
    """The flax MoEFFN module computes identical outputs in dense-local and
    expert-parallel modes (capacity generous enough that nothing drops)."""
    from msrflute_tpu.ops.moe import MoEFFN
    E = expert_mesh.shape["expert"]
    local = MoEFFN(num_experts=E, hidden=16)
    ep = MoEFFN(num_experts=E, hidden=16, ep_mesh=expert_mesh,
                capacity_factor=float(E))  # capacity == local tokens: no drops
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(E * 4, 8)), jnp.float32)
    params = local.init(jax.random.PRNGKey(0), x)["params"]
    y_local = local.apply({"params": params}, x)
    y_ep = ep.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_ep),
                               rtol=2e-5, atol=2e-5)


def test_moe_ringlm_federated_round(mesh8, tmp_path):
    """RingLM with moe_experts rides the ordinary federated engine
    (dense-local expert evaluation under vmap-over-clients)."""
    from msrflute_tpu.config import FLUTEConfig
    from msrflute_tpu.data import ArraysDataset
    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.models import make_task
    rng = np.random.default_rng(0)
    users = [f"u{i}" for i in range(8)]
    per_user = [{"x": rng.integers(1, 32, size=(4, 17)).astype(np.int32)}
                for _ in users]
    ds = ArraysDataset(users, per_user)
    cfg = FLUTEConfig.from_dict({
        "model_config": {"model_type": "RINGLM", "vocab_size": 32,
                         "embed_dim": 16, "num_heads": 2, "head_dim": 8,
                         "mlp_dim": 32, "num_layers": 1, "seq_len": 17,
                         "moe_experts": 4},
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": 2, "num_clients_per_iteration": 4,
            "initial_lr_client": 0.1,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 2, "initial_val": False,
            "data_config": {"val": {"batch_size": 8}},
        },
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.1},
            "data_config": {"train": {"batch_size": 2}},
        },
    })
    task = make_task(cfg.model_config)
    params = task.init_params(jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    assert any("moe_ffn" in jax.tree_util.keystr(path) for path, _ in flat)
    server = OptimizationServer(task, cfg, ds, val_dataset=ds,
                                model_dir=str(tmp_path), mesh=mesh8, seed=0)
    state = server.train()
    assert state.round == 2
    assert "loss" in server.best_val


@pytest.mark.slow
def test_ringlm_sp_with_expert_parallel_moe():
    """Ring attention (sp) + expert-parallel MoE dispatch in ONE model:
    sp_module(expert_axis=...) must match the local module exactly when
    capacity is ample."""
    from jax.sharding import Mesh as _Mesh
    from msrflute_tpu.config import ModelConfig
    from msrflute_tpu.models import make_task
    devs = np.asarray(jax.devices()).reshape(2, 4)
    mesh = _Mesh(devs, ("data", "sequence"))
    mc = {"vocab_size": 40, "embed_dim": 16, "num_heads": 2, "head_dim": 8,
          "mlp_dim": 32, "num_layers": 2, "seq_len": 33, "moe_experts": 4}
    task = make_task(ModelConfig(model_type="RINGLM", extra=mc))
    params = task.init_params(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(0).integers(1, 40, size=(4, 32)),
                    jnp.int32)
    local = task.module.apply({"params": params}, x)
    sp_ep = task.sp_module(mesh, batch_axis="data",
                           expert_axis="sequence").clone(
        moe_capacity_factor=float(4 * 32))  # ample: no drops
    out = sp_ep.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(local), np.asarray(out),
                               rtol=3e-5, atol=3e-5)


# ----------------------------------------------------------------------
# one chip's share of an expert layer: every row-side pass over the pair
# buffer walks the active tiles only (``held_experts_ffn``), against the
# whole-buffer forms it replaced
# ----------------------------------------------------------------------
def _whole_buffer_forms():
    """The forms the row-side passes replaced, each over all ``M`` rows:
    the two gathers with ``mode="fill"``, ``silu(h1) * h3`` as XLA makes
    it, and autodiff's own sum of the two up-products' cotangents."""
    from msrflute_tpu.ops import moe

    @jax.custom_vjp
    def rows_of_tokens(z, pair_of_row, row_of_pair, held):
        return z.at[pair_of_row // row_of_pair.shape[1]].get(
            mode="fill", fill_value=0)

    def rows_fwd(z, pair_of_row, row_of_pair, held):
        return rows_of_tokens(z, pair_of_row, row_of_pair, held), \
            (row_of_pair, held)

    def rows_bwd(saved, d_rows):
        row_of_pair, held = saved
        return jnp.sum(jnp.where(held[..., None], d_rows[row_of_pair], 0),
                       axis=1), None, None, None

    rows_of_tokens.defvjp(rows_fwd, rows_bwd)

    @jax.custom_vjp
    def tokens_of_rows(y_rows, weight, pair_of_row, row_of_pair):
        return jnp.einsum("tkd,tk->td", y_rows[row_of_pair], weight)

    def tokens_fwd(y_rows, weight, pair_of_row, row_of_pair):
        return tokens_of_rows(y_rows, weight, pair_of_row, row_of_pair), \
            (y_rows, weight, pair_of_row, row_of_pair)

    def tokens_bwd(saved, d_y):
        y_rows, weight, pair_of_row, row_of_pair = saved
        d_weight = jnp.einsum("tkd,td->tk", y_rows[row_of_pair], d_y)
        weight_of_row = weight.reshape(-1).at[pair_of_row].get(
            mode="fill", fill_value=0)
        d_rows = d_y.at[pair_of_row // weight.shape[1]].get(
            mode="fill", fill_value=0) * weight_of_row[:, None]
        return d_rows, d_weight, None, None

    tokens_of_rows.defvjp(tokens_fwd, tokens_bwd)

    def ffn(z, router_w, select_bias, w1, w3, w2, per_token):
        chosen, gate = moe.route_tokens(z, router_w, select_bias, per_token)
        row_of_pair, held, pair_of_row, tile_expert, n_active, _ = \
            moe.plan_pairs(chosen, w1.shape[0], 0)
        x_sorted = rows_of_tokens(z, pair_of_row, row_of_pair, held)
        hidden = jax.nn.silu(moe.grouped_matmul(
            x_sorted, w1, tile_expert, n_active)) * moe.grouped_matmul(
                x_sorted, w3, tile_expert, n_active)
        y_sorted = moe.grouped_matmul(hidden, w2, tile_expert, n_active)
        return tokens_of_rows(y_sorted, jnp.where(held, gate, 0.0),
                              pair_of_row, row_of_pair)

    return rows_of_tokens, tokens_of_rows, ffn


#: tokens, experts per token, experts, held experts, the selection bias
#: by expert, the rows whose scalars a step of the gather holds (None:
#: the module's own)
ROW_SIDE_CASES = {
    # held expert 1 is never chosen: its one tile is all padding
    "a_held_expert_without_a_pair": (96, 2, 6, 3, {1: -20.0}, None),
    # expert 0 takes every token's one pair: two tiles of one expert
    "every_pair_on_one_held_expert": (200, 1, 4, 2, {0: 20.0}, None),
    # all experts held: every pair has a row (the worst case)
    "every_pair_held": (150, 3, 4, 4, {}, None),
    # three active tiles, scalars by blocks of two tiles: the last block
    # is half used
    "active_rows_no_multiple_of_the_scalar_block": (150, 2, 3, 3, {}, 256),
    # one of 32 experts held: nine tiles of rows for a dozen pairs
    "tokens_many_times_the_held_pairs": (512, 2, 32, 1, {}, None),
}


@pytest.mark.parametrize("case", list(ROW_SIDE_CASES))
def test_row_side_passes_walk_the_active_tiles_like_the_whole_buffer_forms(
        case, monkeypatch):
    """Values and gradients of ``held_experts_ffn`` (``z``, ``w1``,
    ``w3``, ``w2``, ``router_w``) against the whole-buffer forms; the
    two gathers bit for bit on the rows of the active tiles; the
    counters as the routing has them.  The interpreter leaves NaN on
    the rows beyond the active tiles: nothing may read them."""
    from msrflute_tpu.ops import moe
    tokens, per_token, experts, held_n, favoured, scalar_rows = \
        ROW_SIDE_CASES[case]
    if scalar_rows:
        monkeypatch.setattr(moe, "SCALAR_ROWS", scalar_rows)
    rng = np.random.default_rng(sorted(ROW_SIDE_CASES).index(case))
    dim, width = 32, 16
    z = jnp.asarray(rng.standard_normal((tokens, dim)), jnp.float32)
    router_w = jnp.asarray(rng.standard_normal((dim, experts)) * 0.2,
                           jnp.float32)
    bias = np.zeros((experts,), np.float32)
    for expert, value in favoured.items():
        bias[expert] = value
    bias = jnp.asarray(bias)
    w1, w3 = (jnp.asarray(rng.standard_normal((held_n, dim, width)) * 0.3,
                          jnp.float32) for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((held_n, width, dim)) * 0.3,
                     jnp.float32)
    old_rows, old_tokens, old_ffn = _whole_buffer_forms()
    d_y, target = (jnp.asarray(rng.standard_normal((tokens, dim)),
                               jnp.float32) for _ in range(2))

    def both_ways(new: bool):
        def gathers(z):
            chosen, gate = moe.route_tokens(z, router_w, bias, per_token)
            row_of_pair, held, pair_of_row, _, n_active, _ = \
                moe.plan_pairs(chosen, held_n, 0)
            last = (n_active,) if new else ()
            rows = (moe.rows_of_tokens if new else old_rows)(
                z, pair_of_row, row_of_pair, held, *last)
            d_rows = jax.vjp(lambda y: (
                moe.tokens_of_rows if new else old_tokens)(
                    y, jnp.where(held, gate, 0.0), pair_of_row, row_of_pair,
                    *last), jnp.zeros_like(rows))[1](d_y)[0]
            return rows, d_rows, chosen, n_active, pair_of_row

        def layer(z, router_w, w1, w3, w2):
            if new:
                y, counters = moe.held_experts_ffn(
                    z, router_w, bias, w1, w3, w2,
                    experts_per_token=per_token)
            else:
                y, counters = old_ffn(z, router_w, bias, w1, w3, w2,
                                      per_token), {}
            return jnp.sum(y * target), (y, counters)

        return jax.jit(lambda *args: (gathers(args[0]), jax.value_and_grad(
            layer, argnums=range(5), has_aux=True)(*args)))(
                z, router_w, w1, w3, w2)

    (rows, d_rows, chosen, n_active, pair_of_row), \
        ((_, (y, counters)), grads) = both_ways(True)
    (rows_old, d_rows_old, *_), ((_, (y_old, _)), grads_old) = \
        both_ways(False)
    # the two gathers, bit for bit on the rows of the active tiles
    active = int(n_active) * moe.TILE_ROWS
    for got, want in ((rows, rows_old), (d_rows, d_rows_old)):
        assert np.array_equal(np.asarray(got[:active]),
                              np.asarray(want[:active]))
    # the layer: values and gradients
    for got, want in zip((y, *grads), (y_old, *grads_old)):
        assert np.isfinite(np.asarray(got)).all()
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * float(
            jnp.max(jnp.abs(want)))
    local = np.asarray(chosen)
    loads = [(local == e).sum() for e in range(held_n)]
    assert {k: float(v) for k, v in counters.items()} == {
        "moe_pairs_held": float(sum(loads)),
        "moe_max_load": float(max(loads)),
        "moe_pairs_dropped": 0.0, "moe_layer_steps": 1.0,
        "moe_tiles_active": float(sum(
            max(-(-c // moe.TILE_ROWS), 1) for c in loads))}
    if case == "a_held_expert_without_a_pair":
        assert loads[1] == 0 and not np.asarray(grads[2][1]).any()
    if case == "every_pair_on_one_held_expert":
        assert loads == [tokens, 0]
    if case == "every_pair_held":
        assert sum(loads) == tokens * per_token
    if case == "active_rows_no_multiple_of_the_scalar_block":
        assert active % scalar_rows and active > scalar_rows
    if case == "tokens_many_times_the_held_pairs":
        assert len(pair_of_row) >= 8 * active

"""The expert product of a layer that holds a SHARE of its experts
(``fedml_tpu/models/lfm2_moe.py::expert_product`` with ``n_held <
n_experts``): it runs over the held experts' sorted slots alone, in row
blocks, up to the last held slot — every held slot computed, none dropped,
whatever the load — and the layer that holds every expert keeps the program
it had.  On the CPU at small widths with seeded weights.

Tolerance: the product is float32 on the CPU against a float64 dense loop;
a token's k slots are summed in another order: 2e-5 absolute on values and
gradients of order 1."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import obs
from fedml_tpu.models import lfm2_moe
from fedml_tpu.obs import scopes
from test_lfm2_moe import _expert_layer

D, WIDTH = 24, 8          # 24 = 3 x 8: the combine folds a row in three


def _weights(rs, n_held):
    mk = lambda *s: 0.3 * rs.randn(*s)
    return mk(n_held, D, WIDTH), mk(n_held, D, WIDTH), mk(n_held, WIDTH, D)


def _routing(rs, load, first, n_held, n_experts, k, n):
    """sel [n, k] with the held experts' slots at the named load, and the
    number of held slots."""
    held = np.arange(first, first + n_held)
    absent = np.setdiff1d(np.arange(n_experts), held)
    R = lfm2_moe.block_rows(n * k, n_held, n_experts)
    n_valid = {"none": 0, "uniform": n * k * n_held // n_experts,
               "just_over_one_block": R + 1, "every_slot": n * k}[load]
    assert n_valid <= n * k
    flat = np.concatenate([rs.choice(held, n_valid), rs.choice(absent, n * k - n_valid)])
    return rs.permutation(flat).reshape(n, k).astype(np.int32), n_valid


def _dense(f, sel, gate, w1, w3, w2, first):
    """Every held slot with plain products at its expert's own matrices, in
    the dtype of the arguments; an absent expert's slot adds nothing."""
    n_held = w1.shape[0]
    m = 0.0
    for j in range(sel.shape[1]):
        e = sel[:, j] - first
        held = (e >= 0) & (e < n_held)
        e = jnp.clip(e, 0, n_held - 1)
        h = (jax.nn.silu(jnp.einsum("td,tdw->tw", f, w1[e]))
             * jnp.einsum("td,tdw->tw", f, w3[e]))
        y = jnp.einsum("tw,twd->td", h, w2[e])
        m = m + jnp.where(held[:, None], gate[:, j, None] * y, 0.0)
    return m


def _reference(f, sel, gate, weights, first, probe):
    """(m, df, dgate) of the dense loop in float64."""
    with jax.enable_x64():
        f, gate, probe, *weights = (jnp.asarray(a, jnp.float64)
                                    for a in (f, gate, probe, *weights))
        sel = jnp.asarray(sel)
        m = _dense(f, sel, gate, *weights, first)
        df, dgate = jax.grad(lambda f, g: jnp.sum(probe * _dense(
            f, sel, g, *weights, first)), (0, 1))(f, gate)
        return tuple(np.asarray(a) for a in (m, df, dgate))


# (first, n_held, n_experts, k, N): R = 1024 rows at S = 2048, 2560 and 2048
SHARES = [(2, 2, 8, 2, 1024), (0, 3, 12, 4, 640), (5, 3, 8, 1, 2048)]
LOADS = ["none", "uniform", "just_over_one_block", "every_slot"]


@pytest.mark.parametrize("load", LOADS)
@pytest.mark.parametrize("first, n_held, n_experts, k, n", SHARES)
def test_blocked_product_equals_a_dense_loop_over_the_held_experts(
        first, n_held, n_experts, k, n, load):
    """Forward, df and dgate against the float64 dense loop, from no held slot
    to every slot held by the subset (the loop then runs S / R blocks: what
    the parent's product did); the counter's rows follow the load."""
    rs = np.random.RandomState(7)
    weights = _weights(rs, n_held)
    f, gate, probe = rs.randn(n, D), rs.rand(n, k), rs.randn(n, D)
    sel, n_valid = _routing(rs, load, first, n_held, n_experts, k, n)
    want = _reference(f, sel, gate, weights, first, probe)
    f, gate, probe, *weights = (jnp.asarray(a, jnp.float32)
                                for a in (f, gate, probe, *weights))
    product = lfm2_moe.expert_product(first, n_held, n_experts)
    m = product(f, sel, gate, *weights)
    df, dgate = jax.grad(lambda f, g: jnp.sum(probe * product(
        f, sel, g, *weights)), (0, 1))(f, gate)
    for got, ref in zip((m, df, dgate), want):
        np.testing.assert_allclose(got, ref, atol=2e-5)
    if load == "none":
        assert not np.asarray(m).any() and not np.asarray(df).any()
    else:
        assert np.abs(want[0]).max() > 0.1 and np.abs(want[2]).max() > 0.1
    # the rows the blocks ran: whole blocks, up to the last held slot
    R = lfm2_moe.block_rows(n * k, n_held, n_experts)
    lp = dict(zip(("w1", "w3", "w2"), weights), router=jnp.zeros((D, n_experts)))
    _, counts = lfm2_moe.held_share(f, sel, gate, lp, first, n_held)
    ran, routed = np.asarray(counts[scopes.MOE_SLOT_ROWS])
    assert routed == n * k and ran == -(-n_valid // R) * R
    assert ran >= n_valid and ran - n_valid < R
    assert {"none": 0, "uniform": 1, "just_over_one_block": 2}.get(
        load, -(-n * k // R)) == ran // R


@pytest.mark.parametrize("first, n_held, n_experts, k, n", SHARES[:2])
def test_a_chunk_of_clients_is_one_merged_blocked_product(first, n_held, n_experts,
                                                          k, n):
    """Under the engine's vmap over 2 clients, inside `jax.checkpoint` inside
    `jax.grad`, the clients' tokens are merged, one block loop runs over
    them, and every client's loss and gradients are those of its own call."""
    rs = np.random.RandomState(8)
    weights = [jnp.asarray(w, jnp.float32) for w in _weights(rs, n_held)]
    c = 2
    f = jnp.asarray(rs.randn(c, n // 2, D), jnp.float32)
    gate = jnp.asarray(rs.rand(c, n // 2, k), jnp.float32)
    sel = jnp.asarray(np.stack([_routing(rs, "uniform", first, n_held, n_experts,
                                         k, n // 2)[0] for _ in range(c)]))
    product = lfm2_moe.expert_product(first, n_held, n_experts)
    loss = lambda f, s, g: jnp.sum(jax.checkpoint(product)(f, s, g, *weights) ** 2)
    mapped = jax.jit(jax.vmap(jax.value_and_grad(loss, (0, 2))))
    got_l, got_g = mapped(f, sel, gate)
    for i in range(c):
        want_l, want_g = jax.value_and_grad(loss, (0, 2))(f[i], sel[i], gate[i])
        np.testing.assert_allclose(got_l[i], want_l, rtol=1e-5)
        for a, b in zip(got_g, want_g):
            np.testing.assert_allclose(a[i], b, atol=2e-5)
    # grouped products of ONE block of the merged slots' R rows, never of S
    R = lfm2_moe.block_rows(c * (n // 2) * k, n_held, n_experts)
    text = str(jax.make_jaxpr(mapped)(f, sel, gate))
    rows = [int(m) for m in re.findall(r"f32\[(\d+),\d+\] = ragged_dot_general", text)]
    assert len(rows) >= 8 and set(rows) == {R}, rows
    # and the merged product's rows, summed over the clients
    lp = dict(zip(("w1", "w3", "w2"), weights), router=jnp.zeros((D, n_experts)))
    counts = jax.vmap(lambda f, s, g: lfm2_moe.held_share(
        f, s, g, lp, first, n_held)[1][scopes.MOE_SLOT_ROWS])(f, sel, gate)
    held = int(((np.asarray(sel) >= first) & (np.asarray(sel) < first + n_held)).sum())
    assert float(counts[:, 0].sum()) == -(-held // R) * R
    assert float(counts[:, 1].sum()) == sel.size


def test_a_held_share_layer_makes_no_float_array_as_long_as_the_slots():
    """The structural pin: in the lowered text of a held-share layer's
    forward and backward pass no float array [S, ...] exists, S = k x tokens
    — S long are the integer keys and the order, and the gates and their
    gradient, the layer's own [tokens, k] read flat, one float a slot; the
    layer that holds every expert still gathers, multiplies and un-sorts
    [S, width] floats (the parent's program)."""
    rs = np.random.RandomState(9)
    n, k, n_experts = 640, 4, 8
    S = n * k                                     # 2,560: no other dimension
    f = jnp.asarray(rs.randn(n, D), jnp.float32)

    def lowered(held):
        lp = _expert_layer(rs, n_experts, D, WIDTH)
        lp.update({w: lp[w][held[0]:held[1]] for w in ("w1", "w3", "w2")})
        grad = jax.grad(lambda f: jnp.sum(lfm2_moe.moe_layer(
            f, lp, k, 1.0, held=held)[0] ** 2))
        return jax.jit(grad).lower(f).as_text()

    def leading(text, rank):            # dtypes of the arrays [S] or [S, ...]
        dims = r"\d+x(?:\d+x)*" if rank > 1 else ""
        return set(re.findall(rf"tensor<{S}x{dims}([a-z]+\d+)>", text))

    share = lowered((2, 4))
    assert leading(share, 1) == {"i32", "i1", "f32"}
    assert leading(share, 2) <= {"i32", "i1"}, leading(share, 2)
    assert "f32" in leading(lowered((0, n_experts)), 2)


# `jax.make_jaxpr` of the gradient through `moe_layer` with every expert
# held, under a checkpoint and a vmap over 4 clients, at lfm2moe24b's shapes
# (4 x 2,048 tokens of 2,048, 64 experts of 1,536, 4 a token), traced on the
# parent commit 07ad60c (builder, CPU, PR 43; function addresses blanked)
PARENT_JAXPR = (1115, "b1140ddeb5b5c9ba39cc10666f3e2f526225bc020695f133deae2a76e7900216")


def test_the_all_held_layer_traces_the_parents_jaxpr():
    """A layer that holds every expert has nothing to skip and keeps the
    parent's program, line for line."""
    d, n_experts, width, k, T, clients = 2048, 64, 1536, 4, 2048, 4
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    lp = {"router": S(d, n_experts), "expert_bias": S(n_experts),
          "w1": S(n_experts, d, width), "w3": S(n_experts, d, width),
          "w2": S(n_experts, width, d)}

    def loss(f, lp):
        m, _ = jax.checkpoint(lambda f, lp: lfm2_moe.moe_layer(f, lp, k, 1.0))(f, lp)
        return jnp.sum(m.astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.vmap(jax.grad(loss), in_axes=(0, None)))(
        S(clients, 1, T, d), lp))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert (len(text.splitlines()),
            hashlib.sha256(text.encode()).hexdigest()) == PARENT_JAXPR
    assert "while" not in text and f"[{clients * T * k}," in text


@pytest.mark.parametrize("n_slots, n_held, n_experts, rows", [
    (8 * 8192, 8, 128, 6144), (6 * 4096, 20, 160, 4608), (2 * 24, 2, 8, 512)])
def test_block_rows_is_a_multiple_of_512_near_the_uniform_expectation(
        n_slots, n_held, n_experts, rows):
    R = lfm2_moe.block_rows(n_slots, n_held, n_experts)
    expected = n_slots * n_held / n_experts
    assert R == rows and R % 512 == 0
    assert R == 512 or expected / 2 <= R <= 2 * expected


def test_slot_rows_reach_obs_through_the_program_counters():
    """`moe_slot_rows` is summed like the routed-token counter — by the
    trainer over the steps, by the engine over the clients — and read through
    ``transfer_stats.program_counters()``, which bumps
    ``moe_slot_rows_total{rows="run" | "routed"}``."""
    from test_deepseek_v2 import _engine
    run = obs.counter("moe_slot_rows_total", rows="run")
    routed = obs.counter("moe_slot_rows_total", rows="routed")
    tokens_total = obs.counter("moe_expert_tokens_total")
    before = run.value, routed.value, tokens_total.value
    from fedbench.harness import loop
    engine, build = _engine()
    state = loop.State(engine, build.init_variables(engine), 3)
    engine.transfer_stats.reset()
    assert loop.run_rounds(state, 2, rounds=2)["failed"] == 0
    counters = engine.transfer_stats.program_counters()
    rows, tokens = counters[scopes.MOE_SLOT_ROWS], counters[scopes.MOE_EXPERT_TOKENS]
    model = engine.trainer.model
    first, n_held = model.held_experts
    assert rows.shape == (len(model.expert_layers), 2)
    held = tokens[:, first:first + n_held].sum(axis=1)
    np.testing.assert_array_equal(rows[:, 1], tokens.sum(axis=1))    # k x tokens
    assert (rows[:, 0] >= held).all() and (rows[:, 0] % 512 == 0).all()
    assert run.value - before[0] == rows[:, 0].sum()
    assert routed.value - before[1] == rows[:, 1].sum() == tokens.sum()
    assert tokens_total.value - before[2] == tokens.sum()

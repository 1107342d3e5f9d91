"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # four chips: the mesh path, and nothing else

One process, no children.  Each phase prints one JSON line as it
finishes; any exception propagates (non-zero exit, no last line).  The
last line — printed only when every phase passed — is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "<device_kind>", "count": N}}

Default (one chip), in order:
  (a) device     what JAX attached, versions, the compile cache in force.
                 Anything but a TPU ends the run here, non-zero.
  (b) headline   the recipe of the benchmark's silo cell
                 (resnet18gn.silo128of1024) — ResNet-18-GN (11.2 M
                 params, published widths 64-128-256-512), CIFAR-10
                 shapes, 128 clients x 390 samples, batch 32, one local
                 epoch, bf16 compute — under full participation, over a
                 LEARNABLE seeded stand-in (random labels would pin the
                 loss at ln 10 and prove nothing): 2 warm-up + 3 timed
                 rounds; fails on a non-finite loss, a loss that did not
                 fall, or variables not on the TPU.
      oracle     the repo's own correctness oracle at this width: 8
                 clients x one full batch of 32, E = 1, f32, full
                 participation — one engine round must equal one
                 centralized GD step on the pooled 256 samples, computed
                 by a plain jax.grad step that shares no code with the
                 engine (tests/test_fedavg.py is the CPU twin).
  (c) kernels    the pallas kernels COMPILED by Mosaic (not interpreted),
                 output and gradients in bfloat16: the fused causal
                 attention at two language-model cells' shapes, against a
                 float32 oracle beside the plain path; the rotary kernel at
                 Command A+'s q and k and at latent attention's 64-wide
                 query parts (DeepSeek-V2's, Xing4.0's), against its plain
                 body.
  (d) cli        fedml_tpu.cli.main([...]) in-process: argument parsing
                 -> engine -> history.jsonl on the device.

`--chips 4` runs only the headline cohort on a make_mesh(4) mesh and the
same rounds on make_mesh(1) in this process, as what it is compared
with.  Sizes default to the real ones; tests/test_chip_smoke.py shrinks
them to drive the same functions on the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The real sizes of the default run."""
    model: str = "resnet18_gn"
    n_clients: int = 128
    samples_per_client: int = 390
    batch_size: int = 32
    image_hw: int = 32
    warmup_rounds: int = 2
    timed_rounds: int = 3
    oracle_clients: int = 8
    # (B, T, H, H_kv, head size): a step of ouro2p6b.silo4of256t1024 and a
    # chunk's step of lfm2moe24b.lora4of256t2048
    attn_shapes: tuple = ((2, 1024, 16, 16, 128), (4, 2048, 32, 8, 64))
    # (B, T, H, head size): q and k of a sliding layer of
    # cmdaplus.lora4of256long, and the queries' rotary part in latent
    # attention of deepseekv2.lora4of256t4096 and of xing4.lora4of256long
    rotary_shapes: tuple = ((1, 8192, 128, 128), (1, 8192, 8, 128),
                            (1, 4096, 128, 64), (1, 8192, 32, 64))
    # (B, T, n, C): the four streams of xing4.lora4of256long under one
    # hyper-connection
    hc_shapes: tuple = ((1, 8192, 4, 3584),)
    platform: str = "tpu"        # where every result must live


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _max_abs(tree) -> float:
    import jax
    import jax.numpy as jnp
    return float(max(jnp.max(jnp.abs(a)) for a in jax.tree.leaves(tree)))


def _max_abs_diff(a, b) -> float:
    import jax
    return _max_abs(jax.tree.map(lambda x, y: x - y, a, b))


def _platform_of(tree) -> set:
    import jax
    return {d.platform for leaf in jax.tree.leaves(tree)
            for d in leaf.devices()}


# -- (a) -------------------------------------------------------------------

def phase_device(want_platform: str, want_count: int) -> dict:
    import jax
    import jaxlib

    from fedml_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit("device", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
         compile_cache_dir=cache_dir,
         compile_cache_from_env=bool(os.environ.get(compile_cache.ENV_VAR)))
    if device["platform"] != want_platform:
        raise SystemExit(f"chip_smoke: jax found no {want_platform} "
                         f"(platform {device['platform']!r})")
    if device["count"] != want_count:
        raise SystemExit(f"chip_smoke: needs {want_count} device(s), jax "
                         f"sees {device['count']}")
    return device


# -- (b) -------------------------------------------------------------------

def _learnable_cohort(sz: Sizes, n_clients: int, spc: int, seed: int):
    from fedml_tpu.data.synthetic import synthetic_classification_images
    return synthetic_classification_images(
        n_clients * spc, (sz.image_hw, sz.image_hw), 3, 10, seed=seed)


def build_headline(x, y, n_clients: int = Sizes.n_clients,
                   model_name: str = Sizes.model,
                   batch_size: int = Sizes.batch_size):
    """The headline cell's (cfg, data, trainer) over pre-made samples
    x [n, h, w, 3] / y [n], split evenly over `n_clients` with full
    participation and one local epoch.  The recipe (model, batch size,
    lr, epochs, dtypes, chunk, unroll) is the benchmark's silo cell's,
    fedbench/configs/resnet18gn_cifar.json under
    fedbench/traffic/silo128of1024.json, so the smoke proves the
    program the benchmark times; tests/test_one_instrument.py holds the
    two together field by field."""
    import jax.numpy as jnp

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.federated import (FederatedData, build_client_shards,
                                          build_eval_shard)
    from fedml_tpu.models import create_model
    from fedml_tpu.utils.config import FedConfig

    n = len(y)
    spc = n // n_clients
    cfg = FedConfig(model=model_name, dataset="cifar10",
                    client_num_in_total=n_clients,
                    client_num_per_round=n_clients,
                    epochs=1, batch_size=batch_size, lr=0.1,
                    frequency_of_the_test=10_000)
    idx = {i: np.arange(i * spc, (i + 1) * spc) for i in range(n_clients)}
    ev = build_eval_shard(x[:batch_size], y[:batch_size], batch_size)
    data = FederatedData(
        train_data_num=n, test_data_num=n, train_global=ev, test_global=ev,
        client_shards=build_client_shards(x, y, idx, batch_size),
        client_num_samples=np.full(n_clients, spc, np.float32),
        test_client_shards=None, class_num=10, synthetic=True)
    model = create_model(model_name, output_dim=10)
    # bf16 compute / f32 masters: the MXU fast path (core/trainer.py);
    # batch_unroll=8 unrolls the 13-step batch scan (measured −2.5%:
    # 1.806 vs 1.851 s/round, PERF.md §6 "Before PR 22")
    trainer = ClientTrainer(model, lr=cfg.lr, train_dtype=jnp.bfloat16,
                            batch_unroll=8)
    return cfg, data, trainer


def headline_engine(cfg, data, trainer, mesh=None):
    """MeshFedAvgEngine at the committed recipe — chunk=2 + bf16 local
    masters: the measured v5e optimum (PERF.md §6 "Before PR 22").
    `mesh` defaults to all visible devices."""
    import jax.numpy as jnp

    from fedml_tpu.parallel import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh
    return MeshFedAvgEngine(trainer, data, cfg,
                            mesh=mesh if mesh is not None else make_mesh(),
                            chunk=2, local_dtype=jnp.bfloat16)


class HeadlineRun:
    """Full participation: the cohort IS the whole client stack — upload
    it once (`cohort`, `weights`) and drive the streaming round over it
    (no per-round device-side gather).  Each step() runs one round and
    returns (variables, metrics)."""

    def __init__(self, engine, seed: int = 0):
        import jax
        self.engine = engine
        # placed like every later round's inputs (replicated over the
        # mesh, what run() does): fresh single-device variables would
        # make the FIRST call a program of its own — a second ~2-minute
        # compile and a second 80 MB cache entry of the same round
        # (PR 21 chip runs)
        self.variables = engine._prepare_variables(engine.init_variables())
        self.server_state = engine.server_init(self.variables)
        self.rng = jax.random.PRNGKey(seed)
        self.cohort, self.weights = engine.stream_cohort(0)

    def step(self):
        import jax
        self.rng, r = jax.random.split(self.rng)
        self.variables, self.server_state, m = (
            self.engine.round_fn_streaming(
                self.variables, self.server_state, self.cohort,
                self.weights, r))
        return self.variables, m


def _headline_parts(sz: Sizes, seed: int):
    """build_headline's (cfg, data, trainer) over the learnable stand-in."""
    x, y = _learnable_cohort(sz, sz.n_clients, sz.samples_per_client, seed)
    return build_headline(x, y, n_clients=sz.n_clients,
                          model_name=sz.model,
                          batch_size=sz.batch_size)


def phase_headline(sz: Sizes, seed: int) -> None:
    import jax

    from fedml_tpu.parallel.mesh import make_mesh
    engine = headline_engine(*_headline_parts(sz, seed), mesh=make_mesh(1))
    t0 = time.perf_counter()
    run = HeadlineRun(engine, seed=seed)
    jax.block_until_ready(run.cohort)
    upload_s = time.perf_counter() - t0
    step = run.step

    losses = []
    t0 = time.perf_counter()
    variables, m = step()
    jax.block_until_ready(variables)
    first_round_s = time.perf_counter() - t0      # compile + one round
    losses.append(m["train_loss"])
    for _ in range(sz.warmup_rounds - 1):
        variables, m = step()
        losses.append(m["train_loss"])
    jax.block_until_ready(variables)

    t0 = time.perf_counter()
    for _ in range(sz.timed_rounds):
        variables, m = step()
        losses.append(m["train_loss"])
    jax.block_until_ready(variables)
    t_block = time.perf_counter() - t0
    float(m["train_loss"])                         # the scalar fetch
    t_fetch = time.perf_counter() - t0

    losses = [float(l) for l in losses]
    s_per_round = t_fetch / sz.timed_rounds
    stats = jax.devices()[0].memory_stats() or {}
    emit("headline", model=sz.model, clients=sz.n_clients,
         samples_per_client=sz.samples_per_client, batch_size=sz.batch_size,
         params=sum(a.size for a in jax.tree.leaves(variables)),
         cohort_upload_s=round(upload_s, 3),
         first_round_s=round(first_round_s, 3),
         compile_s=round(first_round_s - s_per_round, 3),
         s_per_round=round(s_per_round, 4),
         s_per_round_block_until_ready_only=round(
             t_block / sz.timed_rounds, 4),
         train_loss=[round(l, 4) for l in losses],
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         variables_platform=sorted(_platform_of(variables)))
    assert all(math.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert _platform_of(variables) == {sz.platform}, _platform_of(variables)


def phase_oracle(sz: Sizes, seed: int) -> None:
    """One f32 engine round == one centralized GD step on the pooled
    samples, parameter-level.  f32 convolutions on the TPU default to
    reduced-precision passes, so BOTH sides run under matmul precision
    "highest" — the check is then tight, not loose."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models import create_model
    from fedml_tpu.parallel import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh

    C, bs, lr = sz.oracle_clients, sz.batch_size, 0.1
    x, y = _learnable_cohort(sz, C, bs, seed + 1)
    cfg, data, _ = build_headline(x, y, n_clients=C,
                                  model_name=sz.model, batch_size=bs)
    model = create_model(sz.model, output_dim=10)
    engine = MeshFedAvgEngine(ClientTrainer(model, lr=lr), data, cfg,
                              mesh=make_mesh(1), donate=False)
    v0 = engine.init_variables()

    def gd_step(variables, xs, ys):
        def loss(params):
            logp = jax.nn.log_softmax(
                model.apply({"params": params}, xs, train=True))
            return -jnp.mean(jnp.take_along_axis(logp, ys[:, None], 1))
        grads = jax.grad(loss)(variables["params"])
        return {"params": jax.tree.map(lambda p, g: p - lr * g,
                                       variables["params"], grads)}

    with jax.default_matmul_precision("highest"):
        cohort, weights = engine.stream_cohort(0)
        v_fed, _, _ = engine.round_fn_streaming(
            v0, engine.server_init(v0), cohort, weights,
            jax.random.PRNGKey(seed))
        v_gd = jax.jit(gd_step)(v0, jnp.asarray(x), jnp.asarray(y, jnp.int32))
    diff = _max_abs_diff(v_fed, v_gd)
    update = _max_abs_diff(v_gd, v0)
    tol = 1e-5
    emit("oracle", model=sz.model, clients=C, pooled_samples=C * bs,
         matmul_precision="highest", max_abs_param_diff=diff,
         max_abs_update=update, tolerance=tol,
         variables_platform=sorted(_platform_of(v_fed)))
    assert _platform_of(v_fed) == {sz.platform}
    assert update > 100 * tol, f"the GD step moved nothing ({update})"
    assert diff <= tol, f"engine round != centralized GD step: {diff}"


# -- (c) -------------------------------------------------------------------

def _lowered_has_kernel(fn, *args) -> bool:
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def _worst_in_ulps(got, want) -> float:
    """max |got - want| in units of `want`'s last bfloat16 place (2^-7 of
    its power of two; 1e-6 where it is smaller than that)."""
    import jax.numpy as jnp
    a, b = got.astype(jnp.float32), want.astype(jnp.float32)
    ulp = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(jnp.abs(b), 1e-30))) - 7)
    return float(jnp.max(jnp.abs(a - b) / jnp.maximum(ulp, 1e-6)))


def phase_kernels(sz: Sizes, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    compiled = sz.platform == "tpu"      # else the ops' plain paths (CPU)

    # fused causal attention (ops/attention.py) at the two language-model
    # cells' shapes, bfloat16: output and the three gradients of the fused
    # path and of the plain path, each against the plain path in float32 at
    # matmul precision "highest".  The kernels may be no farther (relative
    # l2 distance; the largest single error, one rounding of the result on
    # either path, is printed beside it) from that oracle than 1.5 x the
    # plain bfloat16 path is: they differ in where they round (p is
    # normalised after the p.v product, dp stays float32).
    from fedml_tpu.ops import attention
    for B, T, H, H_kv, hd in sz.attn_shapes:
        keys = jax.random.split(jax.random.PRNGKey(seed), 4)
        q32, k32, v32, w = (
            jax.random.normal(key, (B, T, n, hd), jnp.float32)
            for key, n in zip(keys, (H, H_kv, H_kv, H)))

        def both(fn, dtype):
            def loss(q, k, v):
                o = fn(q, k, v)
                return jnp.sum(o.astype(jnp.float32) * w), o
            grads, o = jax.jit(jax.grad(loss, (0, 1, 2), has_aux=True))(
                q32.astype(dtype), k32.astype(dtype), v32.astype(dtype))
            return [a.astype(jnp.float32) for a in (o,) + grads]

        with jax.default_matmul_precision("highest"):
            oracle = both(attention._plain, jnp.float32)
        l2, worst = {}, {}
        for name, fn in (("fused", attention.causal_attention),
                         ("plain", attention._plain)):
            got = both(fn, jnp.bfloat16)
            l2[name] = [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
                        for a, b in zip(got, oracle)]
            worst[name] = [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                           for a, b in zip(got, oracle)]
        kernel = _lowered_has_kernel(
            jax.grad(lambda q, k, v: jnp.sum(
                attention.causal_attention(q, k, v).astype(jnp.float32)),
                (0, 1, 2)),
            *(a.astype(jnp.bfloat16) for a in (q32, k32, v32)))
        emit("kernel", op="causal_attention", shape=[B, T, H, H_kv, hd],
             dtype="bfloat16", compiled=kernel,
             fused_l2_o_dq_dk_dv=l2["fused"], plain_l2_o_dq_dk_dv=l2["plain"],
             fused_max_o_dq_dk_dv=worst["fused"],
             plain_max_o_dq_dk_dv=worst["plain"], tolerance="l2 1.5 x plain")
        assert kernel == compiled, f"causal_attention: kernel path = {kernel}"
        assert all(f <= 1.5 * p_ for f, p_ in zip(l2["fused"], l2["plain"])), l2

    # the rotary kernel (ops/rotary.py) at the q and the k of a sliding
    # layer of cmdaplus.lora4of256long and at the two latent cells' q_rope
    # (heads of 64, two to a row of lanes), bfloat16: output and gradient against
    # the plain body `apply_rotary`.  Both rotate in float32 and round once:
    # they may be one bfloat16 place apart (the two may contract a * b + c
    # differently), as tests/test_rotary_op.py holds them in interpret mode.
    from fedml_tpu.models.looped_lm import rotary_tables
    from fedml_tpu.ops.rotary import apply_rotary, rotate_half
    for shape in sz.rotary_shapes:
        kx, kw = jax.random.split(jax.random.PRNGKey(seed))
        x = jax.random.normal(kx, shape, jnp.float32).astype(jnp.bfloat16)
        w = jax.random.normal(kw, shape, jnp.float32).astype(jnp.bfloat16)
        cos, sin = rotary_tables(shape[1], shape[-1], 5e4)

        def out_and_grad(fn):
            def run(x, w):
                y, transpose = jax.vjp(lambda x: fn(x, cos, sin), x)
                return y, transpose(w)[0]
            return run

        kernel = _lowered_has_kernel(out_and_grad(rotate_half), x, w)
        ulps = [_worst_in_ulps(a, b) for a, b in zip(
            jax.jit(out_and_grad(rotate_half))(x, w),
            jax.jit(out_and_grad(apply_rotary))(x, w))]
        emit("kernel", op="rotate_half", shape=list(shape), dtype="bfloat16",
             compiled=kernel, max_bf16_ulps_y_dx=ulps, tolerance="1 ulp")
        assert kernel == compiled, f"rotate_half: kernel path = {kernel}"
        assert max(ulps) <= 1.0, ulps

    _hc_kernels(sz, seed, compiled)


def _hc_kernels(sz: Sizes, seed: int, compiled: bool) -> None:
    """The four hyper-connection kernels (ops/hyper_connection.py) at the
    streams of xing4.lora4of256long, bfloat16: one hyper-connection around
    ``F(u) = y + u`` with the model's own maps (sigmoids, the Sinkhorn
    loop) - the read's ``u`` and ``ht``, the write's ``X'``, and the
    gradients of ``sum(X' w)`` with respect to the streams and to ``y``
    (the write's backward kernel, then the read's) - from the fused path
    and from the plain path, each against the plain path in float32.  The
    kernels may be no farther (relative l2) from that oracle than 1.5 x the
    plain bfloat16 path is: both sum in float32, the kernels round ``dX``
    once where jax adds the plain path's rounded shares."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models import xing4
    from fedml_tpu.ops import hyper_connection as hc

    for B, T, n, C in sz.hc_shapes:
        k = n * (n + 2)
        keys = jax.random.split(jax.random.PRNGKey(seed), 5)
        X32, y32, w = (jax.random.normal(key, (B, T, width), jnp.float32)
                       for key, width in zip(keys, (n * C, C, n * C)))
        phi32 = 0.02 * jax.random.normal(keys[3], (n * C, k), jnp.float32)
        b = jnp.concatenate([jnp.full((n,), -1.1), jnp.zeros((n,)),
                             xing4.HC_RES_DIAG * jnp.eye(n).reshape(-1)])
        b = b + 0.3 * jax.random.normal(keys[4], (k,), jnp.float32)
        gate = jnp.full((k,), 0.5, jnp.float32)

        def both(read, write, dtype):
            def loss(X, y):
                u, ht, Xc = read(X, phi32.astype(dtype), gate, b, n=n,
                                 eps=1e-6)
                post, res, _ = xing4.hc_maps(
                    jnp.moveaxis(ht, -1, 0), n, 20, 1e-6, (-30.0, 30.0))
                out = write(Xc, y + u, post, res)
                return jnp.sum(out.astype(jnp.float32) * w), (u, ht, out)
            fn = jax.jit(jax.grad(loss, (0, 1), has_aux=True))
            grads, outs = fn(X32.astype(dtype), y32.astype(dtype))
            return fn, [a.astype(jnp.float32) for a in outs + grads]

        plain = (lambda *a, n, eps: hc.read_plain(*a, n, eps), hc.write_plain)
        _, oracle = both(*plain, jnp.float32)
        fn, fused = both(hc.hc_read, hc.hc_write, jnp.bfloat16)
        _, spec = both(*plain, jnp.bfloat16)
        l2 = {name: [float(jnp.linalg.norm(a - o) / jnp.linalg.norm(o))
                     for a, o in zip(got, oracle)]
              for name, got in (("fused", fused), ("plain", spec))}
        kernels = fn.lower(X32.astype(jnp.bfloat16),
                           y32.astype(jnp.bfloat16)).as_text().count(
                               "tpu_custom_call")
        emit("kernel", op="hyper_connection", shape=[B, T, n, C],
             dtype="bfloat16", compiled=bool(kernels), kernels=kernels,
             fused_l2_u_ht_out_dX_dy=l2["fused"],
             plain_l2_u_ht_out_dX_dy=l2["plain"],
             tolerance="l2 1.5 x plain + 1e-6")
        assert kernels == (4 if compiled else 0), (
            f"hyper_connection: kernel path = {kernels} custom calls")
        assert all(f <= 1.5 * p_ + 1e-6
                   for f, p_ in zip(l2["fused"], l2["plain"])), l2


# -- (d) -------------------------------------------------------------------

def phase_cli() -> None:
    """The MNIST/LR recipe of .claude/skills/verify/SKILL.md plus
    --mesh --streaming, through the CLI's own main()."""
    from fedml_tpu import cli
    rounds = 4
    with tempfile.TemporaryDirectory() as run_dir:
        rc = cli.main([
            "--algorithm", "fedavg", "--dataset", "mnist", "--model", "lr",
            "--synthetic_scale", "0.01", "--client_num_in_total", "16",
            "--client_num_per_round", "16", "--comm_round", str(rounds),
            "--batch_size", "8", "--lr", "0.1", "--mesh", "--streaming",
            "--frequency_of_the_test", "1", "--run_dir", run_dir,
            "--run_name", "chip_smoke"])
        path = os.path.join(run_dir, "fedml_tpu", "chip_smoke",
                            "history.jsonl")
        with open(path) as f:
            history = [json.loads(line) for line in f]
    accs = [h["test_acc"] for h in history]
    emit("cli", rc=rc, rounds=len(history), test_acc=accs,
         train_loss=[round(h["train_loss"], 4) for h in history])
    assert rc == 0 and len(history) == rounds, (rc, len(history))
    assert all(math.isfinite(h["train_loss"]) for h in history)
    assert accs[-1] > 0.85 and accs[-1] > accs[0], accs


# -- --chips 4 -------------------------------------------------------------

def phase_four_chip(sz: Sizes, seed: int, n_chips: int = 4) -> None:
    """The headline cohort on a make_mesh(n) mesh against the same
    rounds on make_mesh(1), in this process."""
    import jax

    from fedml_tpu.parallel.mesh import make_mesh
    rounds = 1 + 2                                # 1 warm-up + 2
    parts = _headline_parts(sz, seed)
    out = {}
    for n in (n_chips, 1):
        engine = headline_engine(*parts, mesh=make_mesh(n))
        run = HeadlineRun(engine, seed=seed)
        rows = sorted(s.data.shape[0]
                      for s in run.cohort["x"].addressable_shards)
        losses, times = [], []
        for _ in range(rounds):
            t0 = time.perf_counter()
            variables, m = run.step()
            jax.block_until_ready(variables)
            times.append(time.perf_counter() - t0)
            losses.append(float(m["train_loss"]))
        all_reduce = None
        if n > 1:
            # the program just run, compiled again for its text: a hit
            # in the persistent compile cache, not a second compile
            all_reduce = "all-reduce" in engine.round_fn_streaming.lower(
                variables, (), run.cohort, run.weights,
                jax.random.PRNGKey(0)).compile().as_text()
        out[n] = jax.device_get(variables)
        emit("mesh", chips=n, cohort_rows_per_device=rows,
             all_reduce=all_reduce, first_round_s=round(times[0], 3),
             s_per_round=round(float(np.mean(times[1:])), 4),
             train_loss=[round(l, 4) for l in losses])
        if n > 1:
            assert rows == [sz.n_clients // n] * n, rows
            assert all_reduce, "no all-reduce in the compiled mesh round"
        assert all(math.isfinite(l) for l in losses), losses
    # bf16 local training under another reduction order: 3.7e-3 measured
    # on the four-chip host after three rounds (PR 21)
    rel = _max_abs_diff(out[n_chips], out[1]) / _max_abs(out[1])
    emit("mesh_vs_one_chip", chips=n_chips, rounds=rounds,
         max_abs_diff_over_max_abs=rel, tolerance=1e-2)
    assert rel < 1e-2, rel


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("chip_smoke")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sz = Sizes()
    device = phase_device(sz.platform, args.chips)
    if args.chips == 4:
        phase_four_chip(sz, args.seed)
    else:
        phase_headline(sz, args.seed)
        phase_oracle(sz, args.seed)
        phase_kernels(sz, args.seed)
        phase_cli()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

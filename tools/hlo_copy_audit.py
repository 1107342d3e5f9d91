"""Static HLO copy audit for the engine families' round programs.

The round-2b chip trace (PERF.md) attributes ~0.13 s/round — 7% of leaf
time — to scan-carry/donation copies.  Copies are inserted by
backend-shared XLA passes (layout assignment, while-loop buffer
aliasing, donation/input-output aliasing), so the OPTIMIZED HLO of the
same round program compiled on the virtual-CPU mesh is a faithful
STRUCTURAL proxy for the chip: a carry-layout or donation regression
shows up here as new `copy`/`copy-start` instructions and bytes, without
needing a chip.  (Wall-clock is still priced on the chip.)

For every engine family this tool compiles the family's jitted round
program(s) with the family's real argument placement (sharded stacks,
replicated variables, donated accumulators), walks the optimized module
text for copy instructions, attributes bytes by shape, and emits JSON:

    {family: {copy_ops, copy_bytes, donated_args, aliased_outputs,
              programs: {name: {copy_ops, copy_bytes, ...}}}}

Counting policy: every `copy` and `copy-start` instruction anywhere in
the optimized module (fusion bodies included — on CPU a fused copy still
materializes its tile), bytes = the destination array's shape.  The
numbers are deterministic per jax/jaxlib version, which is why the
regression gate (tests/test_hlo_copy_audit.py) pins ceilings from
benchmarks/hlo_copy_ceilings.json together with the calibration
environment, and names the version skew instead of failing bare when
the toolchain moves.

Usage:
    python tools/hlo_copy_audit.py                      # all families
    python tools/hlo_copy_audit.py --out audit.json
    python tools/hlo_copy_audit.py --families fedavg_resident gossip
    python tools/hlo_copy_audit.py --no-donate          # donation A/B
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

# repo root on sys.path BEFORE any fedml_tpu import: when run as
# `python tools/hlo_copy_audit.py`, sys.path[0] is tools/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DEVICES = 8


def _ensure_cpu(n_devices: int = N_DEVICES) -> None:
    """Force the virtual-CPU platform BEFORE jax backend init: the
    audit is a static census of CPU-compiled HLO and never needs a
    chip."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}")
    import jax
    jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# HLO text analysis
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# an instruction line:  %name = <shape> copy(...)   /  copy-start(...)
_COPY_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*)\s+(copy|copy-start)\(")
_ARRAY_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _first_array_bytes(shape_str: str) -> int:
    """Bytes of the first array in a shape string (for tuples — e.g.
    copy-start's (dest, src, context) — the destination, so the copied
    payload is counted once)."""
    m = _ARRAY_RE.search(shape_str)
    if not m or m.group(1) not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[m.group(1)]


def analyze_hlo_text(txt: str) -> dict:
    """Copy census + aliasing facts of one optimized HLO module."""
    copies = []
    for m in _COPY_RE.finditer(txt):
        copies.append({"shape": m.group(1), "op": m.group(2),
                       "bytes": _first_array_bytes(m.group(1))})
    # alias entries look like `{0, 1}: (3, {}, may-alias)` on the
    # HloModule header line; the pattern is specific enough to scan the
    # whole line (brace-matching the attribute would have to skip the
    # nested `{}` param-index braces anyway)
    header = txt.splitlines()[0] if txt else ""
    donated, outputs = set(), 0
    for _out_idx, param in re.findall(
            r"\{([0-9, ]*)\}:\s*\((\d+),", header):
        outputs += 1
        donated.add(int(param))
    by_shape: dict[str, dict] = {}
    for c in copies:
        s = by_shape.setdefault(c["shape"],
                                {"shape": c["shape"], "count": 0, "bytes": 0})
        s["count"] += 1
        s["bytes"] += c["bytes"]
    top = sorted(by_shape.values(), key=lambda s: -s["bytes"])[:8]
    return {
        "copy_ops": len(copies),
        "copy_bytes": sum(c["bytes"] for c in copies),
        "donated_args": len(donated),
        "aliased_outputs": outputs,
        "top_copies": top,
    }


def audit_program(jit_fn, args) -> dict:
    """Lower + compile one jitted program and analyze its optimized HLO.
    Besides the copy census, the report carries the backend's cost
    analysis (ISSUE 12): `flops` and `bytes_accessed` per dispatch —
    obs/programs.py joins them with live dispatch counts into the
    per-family MFU/bytes-moved accounting (programs.load_census)."""
    compiled = jit_fn.lower(*args).compile()
    report = analyze_hlo_text(compiled.as_text())
    from fedml_tpu.obs.programs import cost_analysis_of
    flops, nbytes = cost_analysis_of(compiled)
    report["flops"] = flops
    report["bytes_accessed"] = nbytes
    return report


# ---------------------------------------------------------------------------
# family round programs
# ---------------------------------------------------------------------------

def _tiny_setup(model: str = "cnn"):
    """Shared tiny workload: 16 clients on 8x8x3 inputs.  Default model
    "cnn": conv kernels/activations are where XLA's layout assignment
    actually inserts carry/staging copies (the LR round is already
    nearly copy-free, so an LR-only census would gate nothing); small
    shapes keep the compile census fast enough for CI."""
    import jax
    from __graft_entry__ import _tiny_data
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models import create_model
    from fedml_tpu.utils.config import FedConfig

    n_clients = 16
    cfg = FedConfig(model=model, client_num_in_total=n_clients,
                    client_num_per_round=n_clients, comm_round=1, epochs=1,
                    batch_size=4, lr=0.1, norm_bound=0.5,
                    frequency_of_the_test=1000)
    data = _tiny_data(n_clients, batch_size=4, hw=8)
    trainer = ClientTrainer(create_model(model, output_dim=10), lr=cfg.lr)
    rng = jax.random.PRNGKey(0)
    return cfg, data, trainer, rng


def build_family_programs(donate: bool = True,
                          families: list[str] | None = None,
                          model: str = "cnn") -> dict:
    """{family: [(program_name, jitted_fn, example_args), ...]} for every
    engine family's round program, built with the family's real argument
    placement.  `families` filters (None = all)."""
    import jax
    import numpy as np
    from fedml_tpu.parallel import (MeshFedAvgEngine, MeshFedNovaEngine,
                                    MeshGossipEngine, MeshHierarchicalEngine,
                                    MeshRobustEngine)
    from fedml_tpu.parallel.mesh import (make_mesh, make_mesh_2d,
                                         replicated_sharding)

    cfg, data, trainer, rng = _tiny_setup(model)
    mesh = make_mesh(N_DEVICES)
    want = (lambda f: families is None or f in families)
    out: dict[str, list] = {}

    def _vars(eng):
        v = eng._prepare_variables(eng.init_variables())
        return v, eng.server_init(v)

    if want("fedavg_resident"):
        eng = MeshFedAvgEngine(trainer, data, cfg, mesh=mesh, donate=donate)
        v, ss = _vars(eng)
        stack, stack_w = eng._device_stack()
        ids, wmask = eng.sample_padded(0)
        # the per-client eval program rides the resident stack (the
        # eval-stack path: _upload_eval_stack placement + vmapped
        # trainer.evaluate) — audited so eval regressions land here too
        # bind the engine at definition (default arg): `eng` is rebound
        # by every later family block, and the jit only traces at AUDIT
        # time — a late-bound closure would evaluate against whichever
        # engine happened to be last (its _x_image_shape state included)
        local_eval = jax.jit(jax.vmap(
            lambda vv, s, _eng=eng: _eng.trainer.evaluate(
                vv, _eng._local_eval_transform(s)), in_axes=(None, 0)))
        out["fedavg_resident"] = [
            ("round", eng.round_fn,
             (v, ss, stack, stack_w, ids, wmask, rng)),
            ("local_eval", local_eval, (v, stack))]

    if want("fedavg_streaming"):
        eng = MeshFedAvgEngine(trainer, data, cfg, mesh=mesh, donate=donate,
                               streaming=True)
        v, ss = _vars(eng)
        cohort, weights = eng.stream_cohort(0)
        # round_fn is the run-loop variant that additionally donates the
        # single-use cohort/weights (round_fn_streaming, the public
        # replay-the-cohort entry, keeps them alive)
        out["fedavg_streaming"] = [
            ("round", eng.round_fn,
             (v, ss, cohort, weights, rng))]

    if want("fedavg_blockstream"):
        eng = MeshFedAvgEngine(trainer, data, cfg, mesh=mesh, donate=donate,
                               stream_block=8)
        v, ss = _vars(eng)
        sums = jax.device_put(eng._zero_sums(v),
                              replicated_sharding(mesh))
        blk, w_blk, r_blk = eng._upload_block(
            np.arange(8), np.ones(8, np.float32),
            np.asarray(jax.random.split(rng, 8)))
        out["fedavg_blockstream"] = [
            ("block_step", eng._block_step, (v, sums, blk, w_blk, r_blk)),
            ("block_finalize", eng._block_finalize, (v, ss, sums, rng))]

    if want("fednova_resident"):
        eng = MeshFedNovaEngine(trainer, data, cfg, mesh=mesh, donate=donate)
        v, ss = _vars(eng)
        stack, stack_w = eng._device_stack()
        ids, wmask = eng.sample_padded(0)
        out["fednova_resident"] = [
            ("round", eng.round_fn,
             (v, ss, stack, stack_w, ids, wmask, rng))]

    if want("robust_orderstat"):
        eng = MeshRobustEngine(trainer, data, cfg, defense="median",
                               n_byzantine=1, mesh=mesh, donate=donate)
        v, ss = _vars(eng)
        stack, stack_w = eng._device_stack()
        ids, wmask = eng.sample_padded(0)
        out["robust_orderstat"] = [
            ("round", eng.round_fn,
             (v, ss, stack, stack_w, ids, wmask, rng))]

    if want("robust_blockstream"):
        eng = MeshRobustEngine(trainer, data, cfg, defense="median",
                               n_byzantine=1, mesh=mesh, donate=donate,
                               stream_block=8, param_block_bytes=16 * 64)
        v, ss = _vars(eng)
        sums = jax.device_put(eng._zero_rest_sums(v),
                              replicated_sharding(mesh))
        blk, w_blk, r_blk = eng._upload_block(
            np.arange(8), np.ones(8, np.float32),
            np.asarray(jax.random.split(rng, 8)))
        P_flat = sum(int(np.prod(a.shape))
                     for a in jax.tree.leaves(v["params"]))
        pb = max(1, ((16 * 64) // (16 * 4) // eng.n_shards) * eng.n_shards)
        xb = jax.device_put(np.zeros((16, pb), np.float32),
                            eng._param_sharding())
        new_flat = jax.numpy.zeros((P_flat,), np.float32)
        out["robust_blockstream"] = [
            ("flats_step", eng._block_step_flats,
             (v, sums, blk, w_blk, r_blk)),
            ("colstat", eng._colstat, (xb,)),
            ("gram", eng._gram, (xb,)),
            ("orderstat_finalize", eng._orderstat_finalize,
             (v, ss, sums, new_flat, rng))]

    if want("hierarchical"):
        mesh2 = make_mesh_2d(n_silos=2, per_silo=4)
        eng = MeshHierarchicalEngine(trainer, data, cfg, mesh=mesh2,
                                     group_comm_round=2, donate=donate)
        v, ss = _vars(eng)
        stack, stack_w = eng._device_stack()
        ids, wmask = eng.sample_inner_rounds(0)
        out["hierarchical"] = [
            ("round", eng.round_fn,
             (v, ss, stack, stack_w, ids, wmask, rng))]

    if want("gossip"):
        eng = MeshGossipEngine(trainer, data, cfg, mesh=mesh, donate=donate)
        wv = eng.init_worker_variables()
        stack, stack_w = eng._device_stack()
        out["gossip"] = [
            ("round", eng.round_fn, (wv, stack, stack_w, rng))]

    if want("twolevel_commit"):
        # the ISSUE-13 two-level multihost aggregation commit: the
        # globally-folded flat f32 carry (the vector that crossed
        # hosts) unflattens, divides, and applies the server update —
        # replicated, O(P), pinned at 0 copy ops with variables +
        # server_state donated (the per-block PARTIAL bodies reuse the
        # streaming round's chunk-scan structure and are covered by the
        # fedavg_* ceilings)
        from fedml_tpu.parallel import MeshFedOptEngine
        from fedml_tpu.parallel.engine import flatten_carry_f32
        eng = MeshFedAvgEngine(trainer, data, cfg, mesh=mesh,
                               donate=donate)
        v, ss = _vars(eng)
        eng._ensure_twolevel()
        flat0, _ = flatten_carry_f32(eng._zero_sums(v))
        flat = jax.device_put(np.zeros(flat0.shape, np.float32),
                              replicated_sharding(mesh))
        # FedAvg's commit REPLACES the global model, so its donated
        # variables are dead (nothing to alias); FedOpt's commit reads
        # them (pseudo-gradient) and carries adam moments — the alias
        # floor of the family comes from this program
        cfg_opt = type(cfg)(**{**cfg.__dict__,
                               "server_optimizer": "adam",
                               "server_lr": 0.05})
        engo = MeshFedOptEngine(trainer, data, cfg_opt, mesh=mesh,
                                donate=donate)
        vo, sso = _vars(engo)
        engo._ensure_twolevel()
        flato = jax.device_put(np.zeros(flat0.shape, np.float32),
                               replicated_sharding(mesh))
        out["twolevel_commit"] = [
            ("commit", eng._twolevel_commit, (v, ss, flat, rng)),
            ("commit_fedopt", engo._twolevel_commit,
             (vo, sso, flato, rng))]

    if want("async_commit"):
        # the async federation's staleness-discounted commit program
        # (fedml_tpu/async_/staleness.py): donated variables + a flat
        # [K, P] buffer-row matrix — the flat-carry layout, so a
        # relayout/donation regression in the commit shows up here like
        # the round programs' (ISSUE 5 acceptance gate)
        import jax.numpy as jnp
        from fedml_tpu.async_.staleness import flat_dim, make_commit_fn
        v = trainer.init(rng, jnp.asarray(data.client_shards["x"][0, 0]))
        K = 8
        commit = make_commit_fn(v, mode="polynomial", a=0.5,
                                donate=donate)
        rows = jnp.zeros((K, flat_dim(v)), jnp.float32)
        w = jnp.ones((K,), jnp.float32)
        s = jnp.zeros((K,), jnp.float32)
        out["async_commit"] = [
            ("commit", commit, (v, rows, w, s, jnp.float32(1.0)))]

    if want("async_bucket_commit"):
        # the ISSUE-9 bucketed robust streaming commit: B seeded bucket
        # accumulators combined via a per-coordinate trimmed mean across
        # bucket means, O(B·P) — pinned at 0 copy ops with variables,
        # accs AND wsums donated (accs aliases the bucket_means stats
        # passthrough), so the defense layer cannot silently reintroduce
        # a params-sized copy into the ingestion hot path
        import jax.numpy as jnp
        from fedml_tpu.async_.staleness import (flat_dim,
                                                make_bucket_commit_fn)
        v = trainer.init(rng, jax.numpy.asarray(
            data.client_shards["x"][0, 0]))
        B = 4
        commit = make_bucket_commit_fn(v, combine="trimmed_mean",
                                       trim_k=1, donate=donate)
        accs = jnp.zeros((B, flat_dim(v)), jnp.float32)
        wsums = jnp.ones((B,), jnp.float32)
        out["async_bucket_commit"] = [
            ("bucket_commit", commit,
             (v, accs, wsums, jnp.float32(1.0)))]

    if want("async_stream_commit"):
        # the streaming aggregation-on-arrival commit (ISSUE 6): the
        # [K, P] reduction already happened at arrival time (the jitted
        # fold), so the commit is an O(P) mix of donated variables with
        # ONE flat accumulator row — pinned at 0 copy ops: any relayout
        # or lost alias in the hot ingestion path shows up here
        import jax.numpy as jnp
        from fedml_tpu.async_.staleness import (flat_dim,
                                                make_stream_commit_fn)
        v = trainer.init(rng, jax.numpy.asarray(
            data.client_shards["x"][0, 0]))
        commit = make_stream_commit_fn(v, donate=donate)
        acc = jnp.zeros((flat_dim(v),), jnp.float32)
        out["async_stream_commit"] = [
            ("stream_commit", commit,
             (v, acc, jnp.float32(8.0), jnp.float32(1.0)))]

    return out


ALL_FAMILIES = ("fedavg_resident", "fedavg_streaming", "fedavg_blockstream",
                "fednova_resident", "robust_orderstat", "robust_blockstream",
                "hierarchical", "gossip", "async_commit",
                "async_stream_commit", "async_bucket_commit",
                "twolevel_commit")


def audit_families(families: list[str] | None = None,
                   donate: bool = True, model: str = "cnn") -> dict:
    """Compile + audit the requested families; returns the full report and
    publishes per-family `engine_copy_bytes_compiled` gauges to the obs
    metrics registry."""
    import jax
    import jaxlib
    from fedml_tpu import obs

    progs = build_family_programs(donate=donate, families=families,
                                  model=model)
    fams = {}
    for family, programs in progs.items():
        per = {}
        for name, fn, args in programs:
            per[name] = audit_program(fn, args)
        flops = [p["flops"] for p in per.values()
                 if p.get("flops") is not None]
        nbytes = [p["bytes_accessed"] for p in per.values()
                  if p.get("bytes_accessed") is not None]
        fams[family] = {
            "copy_ops": sum(p["copy_ops"] for p in per.values()),
            "copy_bytes": sum(p["copy_bytes"] for p in per.values()),
            "donated_args": sum(p["donated_args"] for p in per.values()),
            "aliased_outputs": sum(p["aliased_outputs"]
                                   for p in per.values()),
            # ISSUE 12: the family's per-round-dispatch cost census
            # (None when the backend exposes no cost analysis)
            "flops": sum(flops) if flops else None,
            "bytes_accessed": sum(nbytes) if nbytes else None,
            "programs": per,
        }
        obs.gauge("engine_copy_bytes_compiled", family=family).set(
            fams[family]["copy_bytes"])
    return {
        "meta": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend(),
            "n_devices": len(jax.devices()),
            "model": model,
            "donate": donate,
        },
        "families": fams,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--families", nargs="*", default=None,
                    choices=list(ALL_FAMILIES))
    ap.add_argument("--no-donate", action="store_true",
                    help="compile with donation off (A/B the alias maps)")
    ap.add_argument("--model", default="cnn", choices=["cnn", "lr"],
                    help="model family for the census (cnn default: conv "
                         "layouts are where the copies are)")
    ap.add_argument("--out", default=None, help="write JSON here")
    args = ap.parse_args()
    _ensure_cpu()
    from fedml_tpu.utils import compile_cache
    compile_cache.configure()
    report = audit_families(families=args.families,
                            donate=not args.no_donate, model=args.model)
    text = json.dumps(report, indent=2, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)


if __name__ == "__main__":
    main()

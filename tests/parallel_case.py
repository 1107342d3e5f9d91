"""Shared setup for the mesh-engine test files (test_parallel.py and
test_parallel_stream.py — split so pytest-xdist's per-file scheduling
can run the resident-mesh and streaming/block-stream groups in
parallel workers)."""
import re

from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.data.loaders import load_data
from fedml_tpu.models import create_model
from fedml_tpu.utils.config import FedConfig


def _mnist_like_cfg(**kw):
    base = dict(model="lr", dataset="mnist",
                client_num_in_total=16, client_num_per_round=16,
                comm_round=4, epochs=1, batch_size=16, lr=0.1,
                partition_method="homo", frequency_of_the_test=100)
    base.update(kw)
    return FedConfig(**base)


def _setup(cfg, prox_mu=0.0):
    data = load_data(cfg.dataset, client_num_in_total=cfg.client_num_in_total,
                     batch_size=cfg.batch_size, synthetic_scale=0.02,
                     seed=cfg.seed)
    model = create_model(cfg.model, output_dim=data.class_num)
    trainer = ClientTrainer(model, lr=cfg.lr, optimizer=cfg.client_optimizer,
                            prox_mu=prox_mu)
    return trainer, data


def _token_setup(dataset, model, model_kw, has_time_axis):
    """(trainer, data, cfg) of a tiny synthetic token federation: 8 clients
    of 2 batches of 4 sequences, 4 a round."""
    data = load_data(dataset, client_num_in_total=8, batch_size=4,
                     max_batches_per_client=2, seed=0, synthetic_scale=0.01)
    cfg = FedConfig(model=model, dataset=dataset, client_num_in_total=8,
                    client_num_per_round=4, comm_round=1, epochs=1,
                    batch_size=4, lr=0.1, frequency_of_the_test=100)
    trainer = ClientTrainer(create_model(model, data.class_num, **model_kw),
                            lr=0.1, has_time_axis=has_time_axis)
    return trainer, data, cfg


def run_donate_pair(make_engine, rounds=2):
    """Bitwise donation-correctness pin (ISSUE 4), shared by the resident
    and streaming test files: donation is a memory optimization — the
    SAME program must produce IDENTICAL bits with donate on and off.
    assert_array_equal, not allclose: any drift means donation changed
    the computation, not just the buffers."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    eng_d = make_engine(donate=True)
    v0 = eng_d.init_variables()
    v_don = eng_d.run(variables=jax.tree.map(jnp.copy, v0), rounds=rounds)
    eng_n = make_engine(donate=False)
    v_not = eng_n.run(variables=jax.tree.map(jnp.copy, v0), rounds=rounds)
    for a, b in zip(jax.tree.leaves(v_don), jax.tree.leaves(v_not)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_HLO_INSTRUCTION = re.compile(
    r"\s+(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)")


def hlo_instructions(text: str):
    """(name, result type, opcode, the text from its operands on) of every
    instruction of an optimized HLO module's text, fused bodies included."""
    for line in text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m:
            yield m.groups()


def jaxpr_eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (scan, while, shard_map,
    pjit, custom_vjp bodies) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from jaxpr_eqns(sub)

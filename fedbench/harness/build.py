"""Build a cell's data, trainer and engine from its files.

Everything that reaches ``FedConfig``, ``ClientTrainer`` or the engine is
written in the configuration or traffic file; this module only resolves names
(model factory, engine class, data generator, client-size law) and converts
dtype strings.
"""
from __future__ import annotations

import importlib
import math

import numpy as np

from fedbench import client_sizes as bench_sizes
from fedbench import data as bench_data


def resolve(dotted: str):
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def with_dtypes(args: dict) -> dict:
    """``*_dtype`` strings -> the jnp scalar types the CLI passes (None
    stays None)."""
    import jax.numpy as jnp
    return {k: (getattr(jnp, v) if k.endswith("_dtype") and isinstance(v, str)
                else v) for k, v in args.items()}


def make_data(traffic: dict, seed: int):
    """The cell's FederatedData, made from the seed; no file is read."""
    from fedml_tpu.data.federated import FederatedData
    bs = int(traffic["batch_size"])
    law = traffic["client_sizes"]
    size_law = bench_sizes.resolve(law["law"])
    sizes = size_law.sizes(law, int(traffic["population"]))
    n_batches = math.ceil(size_law.cap(law) / bs)
    ds = traffic["dataset"]
    shards, class_num = bench_data.resolve(ds["generator"])(
        seed, sizes, bs, n_batches, **ds.get("args", {}))
    # the engines upload an eval shard at construction; the window never
    # evaluates, so one batch of the first client stands in for it
    ev = {k: v[0, :1] for k, v in shards.items()}
    return FederatedData(
        train_data_num=int(sizes.sum()), test_data_num=bs,
        train_global=ev, test_global=ev, client_shards=shards,
        client_num_samples=sizes.astype(np.float32),
        test_client_shards=None, class_num=class_num, synthetic=True)


def init_variables(engine):
    """The seeded initial weights, in one jitted call: on the host CPU where
    JAX has that backend beside the chip, because the TPU compiler takes
    13-19 s for the initialisers' program in every run, cache or not, and the
    host 2 s (measured, PR 22); else on the default device."""
    import jax
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:
        return jax.jit(engine.init_variables)()
    with jax.default_device(cpu):
        return jax.jit(engine.init_variables)()


def make_engine(config: dict, traffic: dict, data, seed: int, *,
                train_dtype=None, local_dtype="config"):
    """The engine (with its ``cfg`` and ``trainer``) exactly as the files say.
    The keyword overrides exist for the float32 check round only."""
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.parallel.mesh import make_mesh
    from fedml_tpu.utils.config import FedConfig
    model_doc = config["model"]
    model = resolve(model_doc["factory"])(
        model_doc["name"], data.class_num, **model_doc.get("kwargs", {}))
    trainer_args = dict(config["trainer"])
    engine_args = {**config["engine"], **traffic["engine"].get("args", {})}
    if train_dtype is not None:
        trainer_args["train_dtype"] = train_dtype
    if local_dtype != "config":
        engine_args["local_dtype"] = local_dtype
    cfg = FedConfig(
        model=model_doc["name"], dataset="fedbench:" + traffic["dataset"]["generator"],
        client_num_in_total=data.client_num,
        client_num_per_round=int(traffic["cohort"]),
        epochs=int(traffic["epochs"]), batch_size=int(traffic["batch_size"]),
        lr=float(traffic["lr"]), frequency_of_the_test=10 ** 9, seed=seed,
        batch_unroll=int(trainer_args.get("batch_unroll", 1)),
        train_dtype=str(trainer_args.get("train_dtype", "float32")))
    trainer = ClientTrainer(model, lr=cfg.lr, **with_dtypes(trainer_args))
    return resolve(traffic["engine"]["class"])(
        trainer, data, cfg, mesh=make_mesh(int(traffic["mesh_devices"])),
        **with_dtypes(engine_args))

"""fedml_tpu — a TPU-native federated learning framework.

A ground-up JAX/XLA re-design of the capabilities of FedML (the PyTorch+MPI
reference surveyed in SURVEY.md).  Instead of one OS process per logical
client exchanging pickled state dicts over MPI, clients map to array/mesh-axis
indices: local SGD is a jit-compiled `lax.scan`, cohorts of clients run under
`vmap`/`shard_map` over HBM-sharded partitions, and FedAvg's sample-weighted
aggregation is a weighted tree-mean (a `psum` when sharded over a pod mesh).

Layer map (mirrors SURVEY.md §1, rebuilt TPU-first):

  L5  cli/          entry points (``python -m fedml_tpu.cli.run_fedavg``)
  L4  algorithms/   FedAvg, FedOpt, FedProx, FedNova, robust, hierarchical,
                    decentralized gossip, SplitNN, VFL, FedGKT, FedNAS,
                    TurboAggregate
  L3  models/ data/ flax model zoo + federated dataset loaders (8-tuple
                    contract of the reference)
  L2  core/         ClientTrainer protocol, partitioners, samplers,
                    topology managers, robust aggregation pytree ops
  L1  parallel/     mesh + shard_map federated engine (ICI collectives)
      comm/         host-side message layer (gRPC / in-proc / MQTT) for
                    genuinely remote cross-silo participants
"""

__version__ = "0.1.0"

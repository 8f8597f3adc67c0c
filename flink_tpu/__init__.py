"""flink_tpu — a TPU-native stream-processing framework.

Capabilities modeled on Apache Flink 1.2 (reference: kalmanchapman/flink), but
architected for JAX/XLA on TPU: records are micro-batched into pjit-ed SPMD step
functions over a device mesh; keyed state lives as hash-slot device arrays in HBM
sharded by key group; `keyBy` exchange rides ICI collectives; window updates are
segment-reduce kernels and window fires evaluate whole key panes as single
vectorized kernels.

Layer map (mirrors SURVEY.md §1, re-designed TPU-first):
  core/       — config, types, time, key groups       (ref: flink-core)
  ops/        — device kernels: hashing, hash table, segment reduce, panes
  state/      — state descriptors + backends (device HBM / host heap)
  parallel/   — mesh & shard routing (ICI collectives) (ref: flink-runtime io.network)
  datastream/ — user-facing DataStream API             (ref: flink-streaming-java api)
  graph/      — StreamGraph / JobGraph translation
  runtime/    — executor, checkpoint coordinator, sources/sinks, mini-cluster
  cep/        — pattern matching (vectorized NFA)      (ref: flink-libraries/flink-cep)
"""

__version__ = "0.1.0"

from flink_tpu.datastream.environment import StreamExecutionEnvironment  # noqa: F401

"""nds-tpu: a TPU-native decision-support benchmark framework.

Re-creation of the NDS v2.0 benchmark harness (reference:
willb/spark-rapids-benchmarks) with the GPU (RAPIDS/cuDF) execution path
replaced by a TPU columnar execution engine built on JAX/XLA/Pallas.

Layout:
  schema / dtypes     - typed TPC-DS schema registry (Arrow + device mappings)
  datagen             - native C++ data generator + drivers, query-stream gen
  engine              - SQL frontend -> logical plan -> TPU columnar execution
  ops                 - kernel library (XLA ops + Pallas kernels)
  parallel            - device mesh, sharded execution, distributed exchange
  io                  - CSV/Parquet/columnar IO (Arrow-based)
  lakehouse           - snapshot-based ACID table layer (delta/iceberg parity)
  cli                 - one CLI per benchmark phase (gen_data, transcode,
                        power, maintenance, validate, rollback, bench, ...)
"""

__version__ = "0.1.0"

"""TPU compute kernels (JAX/Pallas) for the storage hot paths:

- gf256: GF(2^8) matrix-multiply over byte streams — the Reed-Solomon
  encode/decode/rebuild engine (replaces klauspost/reedsolomon's SIMD path,
  ref: weed/storage/erasure_coding/ec_encoder.go:198);
- index_kernel: vectorized fid -> (offset, size) probes over sorted index
  snapshots (replaces CompactMap's per-request binary search,
  ref: weed/storage/needle_map/compact_map.go:145).

Also home to the serving-plane load machinery that exercises those paths:

- loadgen: open-loop (Poisson-arrival, coordinated-omission-corrected)
  load generation with zipfian key popularity and log-bucketed latency
  histograms;
- proc_cluster: the multi-PROCESS cluster fixture (every server role a
  real OS process with readiness probes, per-child fault-plan env, and
  no-orphan teardown) plus process-level fault delivery (kill, pause,
  restart on a seeded schedule).
"""

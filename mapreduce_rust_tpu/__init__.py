"""mapreduce_rust_tpu — a TPU-native MapReduce framework.

A from-scratch rebuild of the capabilities of Freebirdgo/MapReduce_Rust
(coordinator/worker runtime, lease-based fault tolerance, hash-partitioned
shuffle, sort-and-group reduce, pluggable map/reduce apps) designed TPU-first:

- Data plane: JAX/XLA. Tokenize→hash runs on-chip over padded uint8 byte
  arrays (segmented associative-scan polynomial hashing), the shuffle is a
  ``lax.all_to_all`` over ICI inside ``shard_map``, and the group-by reduce is
  ``lax.sort`` + neighbour compares. Strings exist only at ingest/egress.
- Control plane: a small asyncio JSON-RPC coordinator preserving the
  reference's scheduler semantics (worker registration barrier, -1/-2/-3
  task sentinels, leases with expiry + re-execution) — see
  ``mapreduce_rust_tpu.coordinator``.

Reference behavior parity is cited per-module against /root/reference
(Freebirdgo/MapReduce_Rust) as ``file:line``.
"""

__version__ = "0.1.0"

from mapreduce_rust_tpu.config import Config  # noqa: F401

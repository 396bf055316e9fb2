"""Device plane: the TPU-native VeloANN engine (DESIGN.md §2).

  index.py        — DeviceIndex: the compressed index as a pytree of arrays
  batch_search.py — batched lockstep cache-aware beam search (lax.scan)
  scan_search.py  — kernel-powered two-stage scan (binary MXU scan -> int4
                    rerank): the beyond-paper TPU mode for sharded corpora
  device_cache.py — HBM record cache with record_map indirection + vectorized
                    clock second-chance (paper §3.2 on device)
  dist_search.py  — shard_map distributed search with top-k merge; the
                    sharded scan's placement and entry (ShardedScan)
  dist_search_ref.py — its plain NumPy reference
"""

"""On-disk cache of what the program builds from a corpus (graph, codes).

An entry is keyed by the configuration's file, the seed and a hash of the
program's build code, so a change to the builder rebuilds instead of reading
a stale index.  Entries live under ``bench/.cache/index/`` (ignored by git);
the oldest are dropped once the directory passes ``MAX_BYTES``, so repeated
runs on fresh seeds keep the disk use bounded.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
from typing import Callable

CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache", "index")
MAX_BYTES = 2 << 30


def code_hash(paths: list[str]) -> str:
    """sha256 over the bytes of each file in ``paths`` (in the order given)."""
    h = hashlib.sha256()
    for p in paths:
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def entry_key(config: dict, seed: int, build_hash: str) -> str:
    blob = json.dumps({"config": config, "seed": int(seed), "code": build_hash},
                      sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def _evict(cache_dir: str, keep: str, max_bytes: int) -> None:
    entries = []
    for name in os.listdir(cache_dir):
        p = os.path.join(cache_dir, name)
        if name.endswith(".pkl") and p != keep:
            st = os.stat(p)
            entries.append((st.st_mtime, st.st_size, p))
    total = sum(e[1] for e in entries) + os.path.getsize(keep)
    for _, size, p in sorted(entries):
        if total <= max_bytes:
            break
        os.remove(p)
        total -= size


def cached(config: dict, seed: int, build_hash: str, build: Callable[[], object],
           cache_dir: str | None = None, max_bytes: int = MAX_BYTES) -> tuple[object, bool]:
    """Returns ``(artifacts, hit)``: the stored artifacts for this key, or
    ``build()``'s result, stored before it is returned.  ``cache_dir``
    defaults to ``CACHE_DIR`` as it stands at the call."""
    cache_dir = CACHE_DIR if cache_dir is None else cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    path = os.path.join(cache_dir, entry_key(config, seed, build_hash) + ".pkl")
    if os.path.exists(path):
        with open(path, "rb") as f:
            return pickle.load(f), True
    obj = build()
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        pickle.dump(obj, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, path)
    _evict(cache_dir, path, max_bytes)
    return obj, False

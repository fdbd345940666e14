"""The checkpoint file of a Loader-attached daemon, in upstream's
``CacheItem`` terms (mailgun/gubernator store.go:29-43): one row a bucket.

    keys        the buckets' hash keys (name + "_" + unique_key), in one
                UTF-8 blob, a newline after each
    algorithm   0 token, 1 leaky
    expire_at   epoch ms
    status, limit, duration, remaining, created_at
                the token bucket's own fields (``TokenBucketItem``)

Seven int64 columns and the blob in one uncompressed ``.npz``, by numpy
alone: 1M rows are 83 MB, made and written in about a second and read in
less (``preload_s``; PERF.md §6, PR 48). ``write`` goes through a temporary file and a rename, so a file that
exists is whole.

A configuration with ``"preload": {"via": "snapshot"}`` is preloaded by
such a file (``preload_rows``: the reference's state after the preload's
own request, never the program's), which ``benchmarks/loader_daemon.py``
reads from ``BENCH_SNAPSHOT_IN``; with ``"shutdown": {"saved": "checked"}``
the server's Loader writes one to ``BENCH_SNAPSHOT_OUT`` and
``check.check_saved`` reads it back. Leaky rows have no place here: a leaky
bucket's remainder is the program's own fixed-point form.

This module imports nothing of the program.
"""

from __future__ import annotations

import os

import numpy as np

from benchmarks.reference.oracle import Reference

FIELDS = ("algorithm", "status", "limit", "duration", "remaining",
          "created_at", "expire_at")


def hash_keys(ks) -> list:
    """The hash key of every key of the keyspace, by id."""
    prefix = ks.name + "_"
    return [prefix + ks.unique_key(k) for k in range(ks.n)]


def preload_rows(ks, hits: int, t_pin: int) -> dict:
    """What the reference holds for every key after the preload's own
    request, ``ks.request(k, hits, created_at=t_pin)``, as columns over the
    key ids. The state does not depend on a key's name, so one evaluation
    per class of key (its flags) stands for all of the class."""
    cols = {f: np.zeros(ks.n, dtype=np.int64) for f in FIELDS}
    for bits in np.unique(ks.flags).tolist():
        of_class = ks.flags == bits
        first = int(np.argmax(of_class))
        ref = Reference()
        ref.get_rate_limits([ks.request(first, hits, created_at=t_pin)], t_pin)
        (row,) = ref.export()
        for f in FIELDS:
            cols[f][of_class] = row[f]
    return cols


def write(path: str, keys: list, cols: dict) -> None:
    blob = np.frombuffer(("\n".join(keys) + "\n" if keys else "").encode(),
                         dtype=np.uint8)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, keys=blob, **{
            c: np.asarray(cols[c], dtype=np.int64) for c in FIELDS})
    os.replace(tmp, path)


def read(path: str) -> tuple:
    """(keys, {field: int64 column}); raises ValueError on a file that is
    not a whole snapshot."""
    try:
        with np.load(path) as z:
            keys = z["keys"].tobytes().decode().split("\n")[:-1]
            cols = {c: z[c] for c in FIELDS}
    except Exception as e:  # whatever numpy or zipfile make of a broken file
        raise ValueError(f"{path}: not a snapshot: {e!r}") from e
    if any(len(v) != len(keys) or v.dtype != np.int64 for v in cols.values()):
        raise ValueError(f"{path}: columns and keys differ in length or type")
    return keys, cols

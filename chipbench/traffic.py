"""The one traffic generator: a mix file's parameters -> seeded items, the
storage that serves them, and the tuner's search space.

A mix (``traffic/<mix>.json``) is data only:

* ``items``: how many sequences the dataset holds (no run wraps an epoch);
* ``order_seed``: the sampler's shuffle seed.  It is fixed by the mix, not
  drawn from the run's seed, so every seed reads the same items in the same
  order and pays the same storage cost; the seed changes the tokens and the
  weights;
* ``storage``: ``{"kind": "memory"}`` or ``{"kind": "latency", ...}`` with
  ``LatencyStorage``'s keyword arguments;
* ``tuner``: the startup DPT search (``autotune``, ``cores``,
  ``max_prefetch``, ``budget_batches``) and the loader's ``initial_workers``.

Sequences are ``seq_len + 1`` int32 tokens drawn uniformly from the
vocabulary; a batch holds ``tokens = item[:-1]``, ``targets = item[1:]``
and a loss mask of ones.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np


def seeds(seed: int) -> Dict[str, int]:
    """Independent 31-bit sub-seeds of a run's ``--seed`` (any size)."""
    s = np.random.SeedSequence(int(seed)).generate_state(2)
    return {"tokens": int(s[0] % 2**31), "weights": int(s[1] % 2**31)}


def make_items(mix: Dict[str, Any], seq_len: int, vocab: int,
               seed: int) -> np.ndarray:
    """The dataset's sequences, ``(items, seq_len + 1)`` int32, from the
    run's seed alone."""
    rng = np.random.default_rng(seeds(seed)["tokens"])
    return rng.integers(0, vocab, (int(mix["items"]), seq_len + 1),
                        dtype=np.int32)


def order(mix: Dict[str, Any], epoch: int = 0) -> np.ndarray:
    """The sampler's order of one epoch, written out plainly: a seeded
    permutation of all items (the ``random`` order of the mix)."""
    if mix.get("order", "random") != "random":
        raise ValueError(f"unknown order {mix['order']!r}")
    rng = np.random.default_rng((int(mix["order_seed"]), epoch))
    return rng.permutation(int(mix["items"]))


def plain_batches(items: np.ndarray, mix: Dict[str, Any], batch: int,
                  first: int, count: int) -> np.ndarray:
    """Tokens of global batches ``first .. first+count-1``: a plain read of
    the items in the sampler's order, ``(count, batch, seq_len + 1)``.
    An epoch is ``items // batch`` whole batches; the next epoch draws a
    new order."""
    per_epoch = len(items) // batch
    out = np.empty((count, batch, items.shape[1]), items.dtype)
    perms: Dict[int, np.ndarray] = {}
    for j, k in enumerate(range(first, first + count)):
        epoch, b = divmod(k, per_epoch)
        if epoch not in perms:
            perms[epoch] = order(mix, epoch)
        out[j] = items[perms[epoch][b * batch:(b + 1) * batch]]
    return out


def _transform(seq_len: int):
    def transform(arr):
        return {"tokens": arr[:-1], "targets": arr[1:],
                "loss_mask": np.ones(seq_len, np.float32)}

    def batch_transform(raw, *, out: Optional[Dict] = None):
        from repro.data.dataset import out_matches
        b = raw.shape[0]
        spec = {"tokens": ((b, seq_len), np.int32),
                "targets": ((b, seq_len), np.int32),
                "loss_mask": ((b, seq_len), np.float32)}
        if not out_matches(out, spec):
            out = {k: np.empty(shape, dtype)
                   for k, (shape, dtype) in spec.items()}
        out["tokens"][...] = raw[:, :-1]
        out["targets"][...] = raw[:, 1:]
        out["loss_mask"][...] = 1.0
        return out

    transform.batch_aware = True
    transform.batch_variant = batch_transform
    return transform


def build_dataset(mix: Dict[str, Any], items: np.ndarray):
    """The program's ``Dataset`` over the mix's storage."""
    from repro.data.dataset import Dataset
    from repro.data.storage import ArrayStorage, LatencyStorage

    storage = ArrayStorage(list(items))
    st = dict(mix["storage"])
    kind = st.pop("kind")
    if kind == "latency":
        storage = LatencyStorage(storage, **st)
    elif kind != "memory" or st:
        raise ValueError(f"unknown storage {mix['storage']!r}")
    return Dataset(storage, transform=_transform(items.shape[1] - 1))

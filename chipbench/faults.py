"""Faults planted underneath a run's timed path (``cell.run_cell``'s
``plant``), to show that the output check catches each: the harness's
tests run them on the CPU, ``calibrate.py`` reads them on the chip."""
from __future__ import annotations


def frozen_state(trainer, items) -> None:
    """The step computes, then returns its input state unchanged."""
    clock = trainer.step_fn
    step = clock.inner

    def frozen(state, batch):
        import jax
        _, metrics = step(jax.tree_util.tree_map(lambda x: x.copy(), state),
                          batch)
        return state, metrics

    clock.inner = frozen


def half_batch(trainer, items) -> None:
    """The step sees the first half of each batch: its loss and gradient
    are means over the rest."""
    clock = trainer.step_fn
    step = clock.inner

    def half(state, batch):
        n = batch["tokens"].shape[0] // 2
        return step(state, {k: v[:n] for k, v in batch.items()})

    clock.inner = half


def altered_token(trainer, items) -> None:
    """The loader's collate changes the first token of every batch."""
    ds = trainer.loader.dataset
    collate = ds.batch_transform
    vocab = int(items.max()) + 1

    def altered(raw, *, out=None):
        out = collate(raw, out=out)
        out["tokens"][0, 0] = (out["tokens"][0, 0] + 1) % vocab
        return out

    ds._batch_transform = altered


ALL = {"frozen_state": frozen_state, "half_batch": half_batch,
       "altered_token": altered_token}

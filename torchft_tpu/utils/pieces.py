"""A gradient handed over in pieces: the one type the compute side
(``parallel/train_step.py``, which makes the pieces) and the exchange
(``ddp.py``, which moves a piece a bucket) both know."""

from __future__ import annotations

import functools

__all__ = ["GradPieces"]


class GradPieces(tuple):
    """A gradient in pieces, in the order they become ready: a tuple of
    pytrees that is itself a pytree (its leaves are the pieces' leaves, in
    that order). ``ddp.allreduce_gradients`` gives back the same pieces,
    averaged; ``TrainStep.grads_tree`` stacks them into the parameters'
    shape."""

    def __new__(cls, pieces):
        _pieces_are_a_pytree()
        return super().__new__(cls, pieces)


@functools.lru_cache(maxsize=None)
def _pieces_are_a_pytree() -> None:
    # at the first piece made, not at import: ddp loads without JAX
    import jax

    jax.tree_util.register_pytree_node(
        GradPieces, lambda pieces: (tuple(pieces), None), lambda _, children: GradPieces(children)
    )

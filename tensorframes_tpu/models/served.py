"""What a served model gives the decode engine: the one seam between
``serving/decode.py`` (admit, join, prepare, step, commit over a paged
KV pool) and a model's own programs.

A model is served by handing :class:`~tensorframes_tpu.serving.
DecodeEngine` (through ``Server.register_decode``) a configuration with
a ``served_model(page_size, horizon)`` method, or a :class:`ServedModel`
itself. The engine asks nothing else of a model:

* **page kinds** (:class:`PageKind`), in table order. A kind is a set of
  layers that share pages: its pool columns (``init(num_pages)``, page
  axis first, page 0 the null page) and how many table entries a
  sequence may hold of it. A ``ring`` kind's entries are reused round
  robin (position ``p`` writes entry ``(p // page_size) % entries``), so
  a sequence never holds more than ``entries`` of its pages however long
  it grows: what a layer that attends a bounded ``window`` needs. The
  first kind is the one ``DecodeConfig.num_pages`` sizes and the one that
  fills; the engine gives every other kind ``max_slots * entries + 1``
  pages.
* **prefill** ``(params, pool, tokens[T], length, *tables) -> (pool,
  first_token)`` and **step** ``(params, pool, tokens[S], pos[S],
  *tables) -> (pool, next_tokens[S])`` or ``(pool, next_tokens,
  stats)``: ``pool`` is the kind's column dict where there is one kind,
  ``{kind.name: columns}`` where there are several, taken DONATED and
  returned; ``tables`` is one int32 array per kind (``[entries]`` for
  prefill, ``[S, entries]`` for the step), padding rows all null. One
  token a step and sequence, greedy. ``stats`` is a dict of small arrays
  the engine turns into counters (``expert_counts`` ``[layers, experts]``
  int32: the live rows' tokens per expert).
* optionally **suffix_prefill** (the prefix cache's join) and
  **page_ops** (``extract, restore, copy_page``: host swap and
  copy-on-extend). Without them ``DecodeConfig(prefix_cache=True)`` /
  ``kv_swap=True`` are refused at ``register_decode``.
* optionally **packed_prefill** ``(params, pool, tokens[T],
  seg_start[B], seg_len[B], tables[B, entries]) -> (pool,
  first_tokens[B])``, for a model with one page kind: several prompts
  in one call, prompt ``b`` at rows ``[seg_start[b], seg_start[b] +
  seg_len[b])`` with positions counted from its own start, written
  through ``tables[b]``, attending only itself; every ``seg_start`` and
  ``T`` a multiple of ``pack_block``; empty segments (``seg_len`` 0) and
  rows outside every segment write the null page; ``first_tokens[b]``
  the greedy token after prompt ``b``. A prompt's first token and KV
  bytes must not depend on where it sits or what is packed beside it
  (a preempted request's re-prefill replays them). Where the model gives
  it and no prefix cache is armed, the engine prefills the cold joins of
  one admission poll together through it, and warms its total-token
  ladder in the one-sequence prefill's place.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

__all__ = ["PageKind", "ServedModel", "served_model_of"]


@dataclasses.dataclass(frozen=True)
class PageKind:
    name: str
    #: table entries a sequence may hold
    entries: int
    #: num_pages -> this kind's pool columns (page axis first)
    init: Callable[[int], Dict[str, Any]]
    #: entries reused round robin: a sequence holds at most ``entries``
    ring: bool = False
    #: positions of context this kind's layers attend (None: all of it);
    #: the engine's pages-walked accounting reads it
    window: Optional[int] = None

    def pages_for(self, positions: int, page_size: int) -> int:
        """Pages of this kind a sequence of ``positions`` KV slots holds."""
        need = -(-int(positions) // int(page_size))
        return min(need, self.entries) if self.ring else need


@dataclasses.dataclass(frozen=True)
class ServedModel:
    vocab_size: int
    max_seq_len: int
    kinds: Tuple[PageKind, ...]
    prefill: Callable
    step: Callable
    suffix_prefill: Optional[Callable] = None
    page_ops: Optional[Tuple[Callable, Callable, Callable]] = None
    #: the kernels (``kernels.KERNELS``) the step traces where the
    #: backend runs them: the engine counts a dispatch of each a step
    kernels: Tuple[str, ...] = ("decode_attn",)
    packed_prefill: Optional[Callable] = None
    #: rows a packed prompt's start and the packed call's length are
    #: multiples of
    pack_block: int = 64

    def __post_init__(self):
        if self.packed_prefill is not None and len(self.kinds) != 1:
            raise ValueError(
                "packed_prefill takes one page table a prompt: a model "
                f"with {len(self.kinds)} page kinds cannot give it"
            )

    def init_pool(self, num_pages: Dict[str, int]):
        """The pool columns: one kind's dict, or a dict of them."""
        cols = {k.name: k.init(int(num_pages[k.name])) for k in self.kinds}
        return cols[self.kinds[0].name] if len(self.kinds) == 1 else cols


def served_model_of(model, page_size: int, horizon: int) -> ServedModel:
    """``model`` as the engine takes it: a :class:`ServedModel` as is, a
    configuration through its ``served_model(page_size, horizon)``."""
    if isinstance(model, ServedModel):
        return model
    build = getattr(model, "served_model", None)
    if build is None:
        raise TypeError(
            f"{type(model).__name__} cannot be served: it is no "
            "ServedModel and has no served_model(page_size, horizon) "
            "(models/served.py says what the decode engine asks of a "
            "model)"
        )
    return build(int(page_size), int(horizon))

"""The seam between the LLM engine and a decoder it serves.

``LlmEngineModel`` (``llm/serving.py``) is built over an
:class:`EngineModel`: the functions the engine's four jitted programs
call, and the cache groups the model's layers fall into. The engine,
its allocator and its front-ends know nothing else of a model, and
nothing under ``llm/`` branches on which model it is.

A cache group is a set of layers whose cache a sequence keeps in the
same way: ``full`` layers keep every block of the context, ``window``
layers only the blocks a sliding window of ``window`` tokens can still
see, ``state`` layers no K/V at all but a recurrent state of fixed size
(a linear-attention layer's matrix a head, ``models/qwen3_next.py``; a
state-space layer's diagonal state a channel, ``models/jamba.py``): ONE
slot of the group's pools a sequence, whatever its length. What a slot
holds and how a step turns it is the model's own: the engine hands out
slots and counts their bytes (``kv_row_bytes``), no more.
Each group has pools of its own (a layer's K and V, the one pool of a
model whose values lie inside its key rows, a state layer's state and
convolution pools, whose leading size is slots and not blocks) and a
sequence has one page-table row a group; a model with one group gets its
tables as ``[max_blocks]`` / ``[B, NB]``, a model with several as
``[G, ...]``, in the order of :attr:`EngineModel.cache_groups`. A
``state`` group's row holds the sequence's slot in column 0 and nothing
else: slot 0 is the trash slot, which the padding rows of a batch
bucket and the warm-up probes name. A slot is claimed at admission,
given back when the sequence ends or is preempted, and what it holds is
written whole by the prefill (a resumed sequence's re-prefill too), so a
new owner finds nothing of the last one. Nothing of a state can be
shared, rolled back or looked ahead into: prefix sharing and speculation
are refused at load beside a ``state`` group, as beside a ``window`` one.

A layer is in the group where it STORES, and need not store at all. A
layer may keep no cache and read the pools another layer writes (a
cross-decoder's attention layers over the one full layer's K/V,
``models/phi4flash.py``: a cached token is kept once and read by eight
layers), or keep none and read none (its gated memory units, which read
another layer's output of the same step). Such a layer is in no group
and its entry of ``init_pages`` is empty; the model's own programs hand
it the pools and the table row of the group it reads. The engine sizes,
allocates and counts what is stored (``stats()``'s blocks and bytes a
group are over the group's ``layers``); what is READ a step beyond that
is the model's to count (``step_counters``). A model may have all three
kinds of group at once: its tables are ``[3, ...]``, a ring beside a
slot, and a preempted sequence gives back and is re-prefilled over all
three.

A cache "layer" need not be a layer of the model. A looped decoder
(``models/ouro.py``: 48 layers run four times a token, each pass with
K/V of its own) keeps one cache a (pass, layer) PAIR, 192 of them, and
keeps them all in ONE pool pair: pair ``p``'s block ``b`` is pool row
``p * NB + b``, and its rolled loops add ``p * NB`` to the lane's table
row before the kernel sees it (an offset block id is a block id). To the
engine that is one ``full`` group of ONE storing layer whose cached
token takes all the pairs' rows: ``init_pages`` returns the pool pair as
that layer's entry (its leading size a multiple of the group's
``num_blocks``, which is all the engine asks of it) and ``()`` for every
other, ``kv_row_bytes`` the bytes over all pairs. The engine allocates
block ids below ``num_blocks`` and every pair uses the same ids at its
own offset; block 0 of every pair is that pair's trash block.

Which kernels run is chosen once, at load (``llm/serving.py``, from
``CLIENT_TPU_LLM_KERNEL`` or the platform), and handed to every program
of the model as one :class:`Kernels`. Every model behind the seam runs
every choice, and none picks a device path by itself.

The optional parts are what the engine's optional features need:
``prefill_suffix`` copy-on-write prefix sharing, ``verify`` speculative
decoding, ``param_specs`` tensor parallelism. A model that lacks one is
refused the feature at load, by the name of the missing part.
"""

import dataclasses
from typing import Any, Callable, Optional, Sequence, Tuple

FULL, WINDOW, STATE = "full", "window", "state"


@dataclasses.dataclass(frozen=True)
class CacheGroup:
    """``kind`` :data:`FULL`, :data:`WINDOW` or :data:`STATE`; ``layers``
    the indices of the model's layers that STORE in the group's pools (a
    layer that only reads them, or keeps nothing, is listed nowhere);
    ``window`` the tokens
    a :data:`WINDOW` layer's query sees, itself included (the engine holds
    ``kv_cache.window_ring_blocks`` blocks a sequence for it). A
    :data:`STATE` group's sequence holds one slot of ``1 + max_active``."""

    kind: str
    layers: Tuple[int, ...]
    window: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class Kernels:
    """The load-time kernel choice. ``name`` is one of
    ``paged_attention.KERNELS``: ``pallas`` (every Pallas kernel
    compiled by Mosaic), ``pallas_interpret`` (the same kernels under
    the Pallas interpreter) or ``fused_xla`` (plain XLA, no Pallas
    kernel anywhere). ``attn`` is that choice's paged attention under
    the one contract of ``models/paged_attention.py`` (``q[B, T, H,
    D]``, ``positions[B, T]``; wrapped per shard under ``tp``): a
    model's decode step calls it with ``T = 1``, its verify step with
    the K+1 rows of a sequence."""

    name: str
    attn: Callable


@dataclasses.dataclass(frozen=True)
class EngineModel:
    """What a decoder gives the engine. Signatures, with ``tables`` as
    the module docstring has them and ``kernels`` a :class:`Kernels`:

    ``init_params(key, config) -> params``
    ``cache_groups(config) -> [CacheGroup]``
    ``init_pages(config, num_blocks: [int per group], block_size) -> pages``
    ``prefill(params, tokens[1, L], tables, pages, last_index, config,
    kernels) -> (logits[1, V], pages)``
    ``decode(params, tokens[B], positions[B], tables, pages, config,
    kernels) -> (logits[B, V], pages[, counters])`` where ``counters``
    is an int32 vector named by ``step_counters``, summed by the engine
    into its ``stats()``. The logits are float32. Which token a lane
    reads (the host's, or the id the step before left on the device)
    and the greedy ``argmax`` over the logits are ``jit_llm_decode``'s
    own, around this call (``llm/serving.py::_build_device_fns``): a
    model sees ``tokens[B]`` and returns logits, as ever
    ``prefill_suffix(params, tokens, tables, pages, last_index,
    start_index, prefix_blocks, config, kernels)``, ``verify(params,
    tokens[B, T], positions, lengths, tables, pages, config, kernels)``,
    ``param_specs(config)``: optional, see the module docstring.
    ``heads(config) -> (n_heads, n_kv_heads)``: what ``tp`` must divide.
    ``init_pages`` returns one entry a layer, a pool or a tuple of pools
    (``(k_pages, v_pages)``; a latent model's one pool holds its values
    inside its key rows; a state layer's ``(state_pool, conv_pool)``,
    ``num_blocks`` of its group being slots; ``()`` for a layer that
    stores nothing), which the engine hands back as it got them.
    ``kv_row_bytes(config) -> [(stored, counted) per group]``: bytes a
    cached token takes in one layer of each group, as its pools store
    it and as the model reads it (rows padded to whole lanes count less
    than they store); without it both are what the pools store. A state
    group's entry is the bytes of one SLOT in one layer.
    """

    name: str
    init_params: Callable
    cache_groups: Callable[[Any], Sequence[CacheGroup]]
    init_pages: Callable
    prefill: Callable
    decode: Callable
    prefill_suffix: Optional[Callable] = None
    verify: Optional[Callable] = None
    param_specs: Optional[Callable] = None
    heads: Optional[Callable] = None
    kv_row_bytes: Optional[Callable] = None
    step_counters: Tuple[str, ...] = ()

    def missing_for(self, *, speculation: bool, prefix_sharing: bool,
                    tp: int) -> Optional[str]:
        """The first feature asked for that this model has no part for,
        as a sentence for a load failure; None if it has them all."""
        wanted = (
            (speculation, "speculation", "verify"),
            (prefix_sharing, "prefix_sharing=True", "prefill_suffix"),
            (tp > 1, f"tp={tp}", "param_specs"),
        )
        for asked, feature, part in wanted:
            if asked and getattr(self, part) is None:
                return (f"model family '{self.name}' gives no `{part}`, "
                        f"which {feature} needs")
        return None

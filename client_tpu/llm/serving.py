"""Repository model type wrapping the continuous-batching engine.

``llm_engine`` is a decoupled KServe v2 model (INPUT_IDS -> one
OUTPUT_IDS token per streamed response — the same wire contract as
``llm_decode``) whose generations share ONE :class:`LlmEngine`: every
concurrent ``execute_decoupled`` call is a sequence in the engine's
running batch, so N concurrent streams cost one batched decode step per
token instead of N serial steps. Served through all streaming surfaces
(decoupled gRPC, OpenAI SSE) untouched — the front-ends just see a
decoupled model.
"""

import math
import os
from typing import Any, AsyncIterator, Dict, Optional

import numpy as np

from client_tpu.llm.engine import EngineConfig, LlmEngine
from client_tpu.server.model_repository import Model
from client_tpu.utils import InferenceServerException


class LlmEngineModel(Model):
    """Continuous-batching LLM generation over the paged KV cache.

    The serving half of ROADMAP item 2: same request/response shape as
    :class:`client_tpu.models.serving.LlmDecodeModel` but backed by the
    shared engine — concurrent generations interleave at every decode
    step rather than running serial single-sequence loops.

    The decoder behind it is an ``EngineModel``
    (``models/engine_model.py``; ``model=``, default the Llama family's):
    the functions the four jitted programs call and the cache groups
    its layers fall into. A model that lacks the part a requested
    feature needs (``verify`` for speculation, ``prefill_suffix`` for
    prefix sharing, ``param_specs`` for ``tp > 1``) fails its load with
    an error that names the part.
    """

    decoupled = True
    max_batch_size = 0
    platform = "jax"
    backend = "jax"
    inputs = [{"name": "INPUT_IDS", "datatype": "INT32", "shape": [-1]}]
    outputs = [{"name": "OUTPUT_IDS", "datatype": "INT32", "shape": [1]}]

    #: speculative-decoding opt-in (repository model attr): None = off,
    #: else ``{"mode": "draft" | "ngram", "k": N, ...}`` — the knobs of
    #: :func:`client_tpu.llm.speculation.build_proposer`
    speculation: Optional[Dict[str, Any]] = None

    #: engine-fatal auto-recovery (tier 1 of the self-healing stack):
    #: when True, warmup wires an :class:`~client_tpu.llm.recovery.
    #: EngineRecovery` controller onto the engine so a fatal device
    #: failure triggers a bounded-retry background reload instead of
    #: closed-until-manual-reload.  The pod coordinator turns this off
    #: and supervises recovery itself (an engine fatal there usually
    #: means the MESH is broken, which a solo reload cannot fix).
    auto_recovery: bool = True

    #: knobs forwarded to the EngineRecovery constructor (repository
    #: model attr, e.g. ``{"max_attempts": 5, "retry_after_s": 2.0}``)
    recovery_options: Optional[Dict[str, Any]] = None

    def __init__(
        self,
        name: str = "llm_engine",
        config=None,
        params=None,
        engine_config: Optional[EngineConfig] = None,
        speculation: Optional[Dict[str, Any]] = None,
        draft_config=None,
        draft_params=None,
        tp: int = 1,
        model=None,
    ):
        if model is None:
            from client_tpu.models import llama

            model = llama.ENGINE_MODEL
            config = config or llama.LlamaConfig.tiny(max_seq_len=512)
        elif config is None:
            raise ValueError(
                f"model family '{model.name}' needs its config= given"
            )
        self._model = model
        self.name = name
        # tensor-parallel width: tp > 1 shards params and the paged KV
        # pool over a "tp" mesh axis resolved against the GLOBAL device
        # list — on a pod this is how one engine spans processes
        self.tp = int(tp)
        self.mesh_plan = None
        # pod hook: wraps (prefill, decode, decode_multi) JUST BEFORE the
        # engine is built — after the warmup probes, which every pod
        # member must run unwrapped and in lockstep (the wrapper is where
        # the coordinator broadcasts each step on the bus)
        self.device_fn_wrapper = None
        if speculation is not None:
            self.speculation = dict(speculation)
        elif type(self).speculation is not None:
            self.speculation = dict(type(self).speculation)
        self._draft_config = draft_config
        self._draft_params = draft_params
        self._config = config
        if engine_config is None:
            # default pool: 8 full-length sequences' worth of blocks —
            # small enough that sustained overload exercises the
            # queue/preemption path, large enough that the genai-perf
            # default workload (64-token prompts, 16 output tokens)
            # never starves
            block_size = 16
            per_seq = (self._config.max_seq_len + block_size - 1) // block_size
            engine_config = EngineConfig(
                block_size=block_size,
                num_blocks=1 + 8 * per_seq,
                max_active=8,
                max_queue=64,
                max_seq_len=self._config.max_seq_len,
            )
        # admission math must see the speculative lookahead the engine
        # will actually use (worst-case K+1 growth per sequence)
        if self.speculation is not None:
            engine_config.spec_k = max(1, int(self.speculation.get("k", 4)))
        self.engine_config = engine_config
        self._params = params
        self.engine: Optional[LlmEngine] = None
        # which ragged paged-attention implementation warmup selected
        # ("pallas" / "pallas_interpret" / "fused_xla");
        # reported in the model config's parameters map
        self.decode_kernel: Optional[str] = None
        self._core = None
        # one recovery controller per model instance, created lazily by
        # the first warmup and re-attached across engine swaps
        self._recovery = None

    def _build_device_fns(self, params, config, engine_config, kernels,
                          donate):
        """The engine's jitted device callables for one kernel choice
        (``engine_model.Kernels``): (prefill, decode, decode_multi).
        ``prefill`` routes start==0 (no shared prefix) through the untouched
        full-prompt path and block-aligned suffixes through
        ``prefill_suffix_into_pages`` with a STATIC power-of-two
        prefix-gather bucket (bounded recompiles, one program per
        (suffix bucket, prefix bucket) pair). ``decode_multi`` (the
        speculative verify step; None when the model does not opt in)
        calls the same ``kernels.attn`` with K+1 rows a sequence.

        ``decode`` is the engine's ``decode_fn`` (``LlmEngine``):
        ``jit_llm_decode`` picks each lane's input token inside the
        program, from the ids the step before left on the device
        (``prev_ids[lane_map]``) or from the host's (``host_tokens``,
        where the map is -1), calls the model's ``decode`` as before,
        and also returns ``argmax(logits, -1)`` as int32, zero-padded to
        the width of ``prev_ids``. The select, the argmax and the padding
        live here, in no model; the width of ``prev_ids`` is fixed
        (``EngineConfig.ids_width``), so there is still one program per
        (batch bucket, table bucket). The logits stay an output and stay
        on the device unless the engine reads them.

        Under a tp mesh plan (``self.mesh_plan``) the same callables are
        built sharded: host args are placed as REPLICATED global arrays
        (on a pod, ``jax.device_put`` cannot reach other processes'
        devices — ``place_global`` can), logits and ids are pinned
        replicated so every process can read them locally (and hand the
        ids back as they are), and the page pool keeps its kv-head
        sharding end to end."""
        import jax
        import jax.numpy as jnp

        model = self._model
        plan = self.mesh_plan
        jit_out = {}
        rep = None
        if plan is not None:
            from client_tpu.parallel import TP_AXIS

            rep = plan.replicated()
            pages_sharding = plan.sharding(None, None, TP_AXIS, None)
            jit_out = {"out_shardings": (rep, pages_sharding)}

        def _host(value, dtype=np.int32):
            if isinstance(value, jax.Array):
                return value  # a step's ids, handed back: already placed
            array = np.asarray(value, dtype=dtype)
            if plan is None:
                return array
            from client_tpu.parallel.executor import place_global

            return place_global(array, rep)

        # The four device programs, jitted under their own names: XLA
        # calls the modules jit_llm_prefill, jit_llm_prefill_suffix,
        # jit_llm_decode and jit_llm_verify, which is how a device trace
        # and a compile log tell them apart.
        # params ride as an explicit jit argument (not a closure): a
        # process-spanning param pytree cannot be closed over — jax
        # forbids baking non-addressable arrays into the jaxpr as
        # constants — and the argument form is identical for the
        # single-process case
        def llm_prefill(params_, tokens, page_table, pages, last_index):
            return model.prefill(
                params_, tokens, page_table, pages, last_index, config,
                kernels,
            )

        def llm_prefill_suffix(params_, tokens, page_table, pages,
                               last_index, start_index, prefix_blocks):
            return model.prefill_suffix(
                params_, tokens, page_table, pages, last_index,
                start_index, prefix_blocks, config, kernels,
            )

        def llm_decode(params_, prev_ids, lane_map, host_tokens, positions,
                       page_tables, pages):
            tokens = jnp.where(
                lane_map >= 0, prev_ids[jnp.maximum(lane_map, 0)],
                host_tokens,
            )
            logits, pages, *counted = model.decode(
                params_, tokens, positions, page_tables, pages, config,
                kernels,
            )
            ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            ids = jnp.pad(ids, (0, prev_ids.shape[0] - ids.shape[0]))
            return (ids, logits, pages, *counted)

        def llm_verify(params_, tokens, positions, lengths, page_tables,
                       pages):
            return model.verify(
                params_, tokens, positions, lengths, page_tables, pages,
                config, kernels,
            )

        donate_kw = {"donate_argnums": (3,)} if donate else {}
        prefill_full = jax.jit(llm_prefill, **donate_kw, **jit_out)
        prefill_suffix = jax.jit(
            llm_prefill_suffix, static_argnums=(6,), **donate_kw, **jit_out
        )
        block_size = engine_config.block_size

        def prefill(tokens, page_table, pages, last_index, start_index):
            tokens = _host(tokens)
            page_table = _host(page_table)
            last = (
                _host(np.int32(last_index)) if plan is not None
                else last_index
            )
            if not start_index:
                return prefill_full(params, tokens, page_table, pages, last)
            from client_tpu.llm.engine import block_bucket

            needed = start_index // block_size
            prefix_blocks = min(
                block_bucket(needed), engine_config.max_blocks_per_seq
            )
            return prefill_suffix(
                params, tokens, page_table, pages, last,
                _host(np.int32(start_index)), prefix_blocks,
            )

        donate_kw = {"donate_argnums": (6,)} if donate else {}
        decode_out = (
            {"out_shardings": (rep, rep, pages_sharding)}
            if plan is not None else {}
        )
        decode_jit = jax.jit(llm_decode, **donate_kw, **decode_out)

        def decode(prev_ids, lane_map, host_tokens, positions, page_tables,
                   pages):
            return decode_jit(
                params, _host(prev_ids), _host(lane_map),
                _host(host_tokens), _host(positions), _host(page_tables),
                pages,
            )

        decode_multi = None
        if self.speculation is not None:
            donate_kw = {"donate_argnums": (5,)} if donate else {}
            decode_multi_jit = jax.jit(llm_verify, **donate_kw, **jit_out)

            def decode_multi(tokens, positions, lengths, page_tables, pages):
                return decode_multi_jit(
                    params, _host(tokens), _host(positions), _host(lengths),
                    _host(page_tables), pages,
                )

        return prefill, decode, decode_multi

    def _resolve_tp_plan(self, config):
        """Validate + resolve the ``{"tp": N}`` mesh for this model.
        Raises :class:`InferenceServerException` (a load failure) when
        the head counts don't divide or the devices aren't there."""
        from client_tpu.parallel import TP_AXIS, sharding as mesh_sharding

        n_heads, n_kv_heads = self._model.heads(config)
        if n_heads % self.tp or n_kv_heads % self.tp:
            raise InferenceServerException(
                f"tp={self.tp} must divide n_heads={n_heads} and "
                f"n_kv_heads={n_kv_heads}"
            )
        try:
            spec = mesh_sharding.MeshSpec.parse({"axes": {TP_AXIS: self.tp}})
            return mesh_sharding.resolve(spec)
        except (
            mesh_sharding.MeshDeclarationError,
            mesh_sharding.MeshUnavailableError,
        ) as e:
            raise InferenceServerException(str(e)) from e

    def _shard_params(self, params, config, plan):
        """Place the param pytree onto the tp mesh per the model's
        ``param_specs`` (global placement: works whether or not the mesh
        spans processes)."""
        import jax
        from jax.sharding import PartitionSpec

        from client_tpu.parallel.executor import place_global

        shardings = jax.tree_util.tree_map(
            lambda entries: plan.sharding(*entries),
            self._model.param_specs(config),
            is_leaf=lambda node: isinstance(node, PartitionSpec),
        )
        return jax.tree_util.tree_map(
            lambda leaf, sharding: place_global(np.asarray(leaf), sharding),
            params,
            shardings,
        )

    def _shard_pages(self, pages, plan):
        """Shard every pool of every layer on the kv-head axis — the tp
        partitioning of the paged cache itself."""
        import jax

        from client_tpu.parallel import TP_AXIS
        from client_tpu.parallel.executor import place_global

        sharding = plan.sharding(None, None, TP_AXIS, None)
        return jax.tree_util.tree_map(
            lambda pool: place_global(np.asarray(pool), sharding), pages
        )

    def warmup(self) -> None:
        import jax

        from client_tpu.models import paged_attention
        from client_tpu.models.engine_model import STATE, Kernels

        config, model = self._config, self._model
        engine_config = self.engine_config
        # kernel selection: env override > platform. The choice is final —
        # the probes below compile and run the smallest shapes the engine
        # serves, and a kernel that cannot serve this host fails the LOAD
        # with the compiler's message (never a quiet step down to another
        # implementation). It reaches every program of the model as one
        # `Kernels` and is reported in the model config.
        name, attn = paged_attention.resolve_decode_attention(
            os.environ.get("CLIENT_TPU_LLM_KERNEL"), jax.default_backend()
        )
        missing = model.missing_for(
            speculation=self.speculation is not None,
            prefix_sharing=engine_config.prefix_sharing,
            tp=self.tp,
        )
        if missing is not None:
            raise InferenceServerException(missing)
        if self._params is None:
            self._params = model.init_params(jax.random.PRNGKey(0), config)
        engine_config.cache_groups = tuple(model.cache_groups(config))
        params = self._params
        plan = None
        if self.tp > 1:
            # resolve the tp mesh against the GLOBAL device list (on a
            # pod that is every member's devices) and shard the params
            # along llama.param_specs; failures here are load failures
            # with operator-grade reasons, never a 500 at first infer
            plan = self._resolve_tp_plan(config)
            self.mesh_plan = plan
            params = self._shard_params(params, config, plan)
        else:
            self.mesh_plan = None

        # Buffer donation lets XLA update the block pool in place (the
        # pool is the whole point — ONE physical cache, not a copy per
        # step); the CPU backend does not implement donation and warns,
        # so only donate on real accelerators.
        donate = jax.default_backend() != "cpu"
        # under tp the kernel runs per-shard via shard_map (GSPMD cannot
        # partition a pallas_call; for the XLA function the wrap pins the
        # no-communication head partitioning). Decode and speculative
        # verify call the one function, so they agree numerically.
        if plan is not None:
            attn = paged_attention.make_tp_attention(attn, plan.mesh)
        max_blocks = engine_config.max_blocks_per_seq
        n_groups = len(engine_config.cache_groups)
        # one table row a cache group, stacked when there are several
        table = np.zeros(
            ([n_groups] if n_groups > 1 else []) + [max_blocks],
            dtype=np.int32,
        )
        prefill, decode, decode_multi = self._build_device_fns(
            params, config, engine_config, Kernels(name, attn), donate,
        )
        # a layer's pools (K and V, or the one pool of a model whose
        # values lie inside its key rows), a cache group, as they are
        # stored (a tp shard holds 1/tp of a page's rows), read off the
        # shapes before a pool exists: from them the paged kernel's tile
        # in pages, which sizes the allocators' runs and the window
        # groups' rings and lets the engine count the kernel's tile
        # stops, and the bytes a cached token takes
        shapes = jax.eval_shape(
            lambda: model.init_pages(
                config, [2] * n_groups, engine_config.block_size)
        )
        group_pools = [
            jax.tree_util.tree_leaves(shapes[group.layers[0]])
            for group in engine_config.cache_groups
        ]
        # (a state group's pools are slots and have no tile: 1)
        tile_pages = [
            1 if group.kind == STATE else paged_attention.pages_per_tile(
                math.prod(pools[0].shape[1:-1]) // self.tp, 1,
                max(pool.shape[-1] for pool in pools), pools[0].dtype,
                len(pools),
            )
            for group, pools in zip(engine_config.cache_groups, group_pools)
        ]
        pages = model.init_pages(
            config, engine_config.group_num_blocks(tile_pages),
            engine_config.block_size,
        )
        if plan is not None:
            pages = self._shard_pages(pages, plan)
        # probe the shapes the engine actually serves (page table
        # all-zeros = every write lands in the reserved trash block): full
        # prefill at the smallest bucket, the ragged decode at block
        # buckets 1 AND multi-block (a kernel whose tiling only breaks at
        # wider widths must fail HERE, not engine-fatally at request
        # time), and — when sharing is on — one suffix prefill so the
        # shared-prefix path is both validated and pre-compiled before
        # the first hit.
        probe_tokens = np.zeros(
            [1, engine_config.prefill_bucket_min], dtype=np.int32
        )
        try:
            logits, pages = prefill(
                probe_tokens,
                table,
                pages,
                engine_config.prefill_bucket_min - 1,
                0,
            )
            if engine_config.prefix_sharing and max_blocks > 1:
                logits, pages = prefill(
                    probe_tokens,
                    table,
                    pages,
                    engine_config.prefill_bucket_min - 1,
                    engine_config.block_size,
                )
            one = np.zeros([1], dtype=np.int32)
            ids = np.zeros([engine_config.ids_width], dtype=np.int32)
            for nb in {1, min(8, max_blocks)}:
                # the second probe takes the first one's ids as the
                # device array they are, as a step that runs ahead does
                ids, logits, pages = decode(
                    ids, one, one, one, table[..., None, :nb], pages,
                )[:3]
            if decode_multi is not None:
                # probe the verify shape too (T=2: one real token + one
                # draft) — all writes land in the trash block
                logits, pages = decode_multi(
                    np.zeros([1, 2], dtype=np.int32),
                    np.zeros([1, 2], dtype=np.int32),
                    np.zeros([1], dtype=np.int32),
                    table[None, :1],
                    pages,
                )
            jax.block_until_ready(logits)
        except Exception as e:  # noqa: BLE001 - any compile/run failure
            raise InferenceServerException(
                f"warmup probes failed with decode_kernel='{name}': {e}"
            ) from e
        self.decode_kernel = name
        proposer = None
        if self.speculation is not None:
            from client_tpu.llm.speculation import build_proposer

            draft_params, draft_config = self._draft_params, self._draft_config
            if self.speculation.get("draft") == "self":
                # the draft IS the target (self-speculation): the
                # near-100%-acceptance regime that measures the verify
                # machinery's ceiling — proposals cost a full target
                # forward, so this is a bench/diagnostic mode, not a
                # production speedup config
                draft_params, draft_config = params, config
            # a malformed speculation declaration fails HERE (warmup is
            # the model-load error surface), never at request time
            proposer = build_proposer(
                self.speculation,
                target_config=config,
                draft_params=draft_params,
                draft_config=draft_config,
            )
        # followers (pod workers) drive these directly off the bus; the
        # tuple is captured BEFORE any wrapper so a worker's handlers
        # never re-broadcast
        self._device_fns = (prefill, decode, decode_multi)
        if self.device_fn_wrapper is not None:
            # pod coordinator hook: wrap AFTER the probes (which every
            # member ran unwrapped, in lockstep) so only real engine
            # steps ride the bus
            prefill, decode, decode_multi = self.device_fn_wrapper(
                prefill, decode, decode_multi
            )
        # a reload replaces the engine wholesale: fresh pool, clean
        # accounting (the old engine's streams were drained by the
        # lifecycle layer before the swap)
        if self.engine is not None:
            self.engine.close()
        if model.kv_row_bytes is not None:
            kv_row_bytes = model.kv_row_bytes(config)
        else:  # counted as stored (a state group's entry is a slot's)
            stored = [
                sum(math.prod(pool.shape[1:]) * pool.dtype.itemsize
                    for pool in pools)
                // (1 if group.kind == STATE else engine_config.block_size)
                for group, pools in zip(
                    engine_config.cache_groups, group_pools)
            ]
            kv_row_bytes = [(size, size) for size in stored]
        self.engine = LlmEngine(
            prefill,
            decode,
            pages,
            engine_config,
            model_name=self.name,
            decode_multi_fn=decode_multi,
            proposer=proposer,
            step_counters=model.step_counters,
            attn_tile_pages=tile_pages,
            kv_row_bytes=kv_row_bytes,
        )
        self._core = None  # rebind metrics/executor after a reload
        self._wire_recovery()

    def _wire_recovery(self) -> None:
        """Attach the auto-recovery controller to the (possibly brand
        new) engine.  The controller itself re-attaches after ITS
        reloads; this covers the initial warmup and manual reloads."""
        if not self.auto_recovery:
            return
        if self._recovery is None:
            from client_tpu.llm.recovery import EngineRecovery

            self._recovery = EngineRecovery(
                self, **dict(self.recovery_options or {})
            )
        self._recovery.attach(self.engine)

    def reload(self) -> None:
        """Rebuild device state from scratch: fresh KV pool, re-probed
        kernels, a new engine.  Calls :meth:`warmup` through the CLASS
        so the pod coordinator's instance-level warmup pin (the lockstep
        no-op) never swallows a real reload."""
        type(self).warmup(self)

    @property
    def recovering(self) -> bool:
        """True while a background engine reload is in flight (surfaced
        in ``debug_state()`` and the ``tpu_server_state`` overlay)."""
        from client_tpu.llm import recovery

        return (
            self._recovery is not None
            and self._recovery.state == recovery.RECOVERING
        )

    def config(self) -> Dict[str, Any]:
        """Model config with the warmup-selected decode kernel, the
        prefix-sharing mode, and the speculation declaration in the
        parameters map (Triton ModelParameter wire shape — both
        protocols surface it, like the mesh topology does for sharded
        models).

        ``speculation_stats`` carries the engine's LIVE speculation
        counters as a JSON string: the proto statistics schema is
        frozen, so the config parameters map is the one schemaless
        channel a remote harness (genai-perf ``--json-summary``) can
        delta before/after a run to report tokens-per-step and
        acceptance rate over exactly that run."""
        import json

        doc = super().config()
        parameters = doc.setdefault("parameters", {})
        parameters["decode_kernel"] = {
            "string_value": self.decode_kernel or "uninitialized"
        }
        parameters["tp"] = {"string_value": str(self.tp)}
        parameters["prefix_sharing"] = {
            "string_value": (
                "cow" if self.engine_config.prefix_sharing else "off"
            )
        }
        if self.speculation is None:
            parameters["speculation"] = {"string_value": "off"}
        else:
            parameters["speculation"] = {
                "string_value": json.dumps(
                    self.speculation, sort_keys=True
                )
            }
            if self.engine is not None:
                stats = self.engine.stats()
                parameters["speculation_stats"] = {
                    "string_value": json.dumps(
                        {
                            key: stats[key]
                            for key in (
                                "steps",
                                "lane_steps",
                                "step_tokens",
                                "spec_steps",
                                "spec_proposed",
                                "spec_accepted",
                            )
                        },
                        sort_keys=True,
                    )
                }
        return doc

    def shutdown(self) -> None:
        """Stop the engine's step loop (``ServerCore.close`` hook)."""
        if self.engine is not None:
            self.engine.close()

    def bind_core(self, core) -> None:
        """Wire the engine into the server it serves under (called by
        ``ServerCore.infer_decoupled`` on first use): metrics export via
        the shared registry, device calls on the core's executor, errors
        into the structured logger. Idempotent per core."""
        if self._core is core or self.engine is None:
            return
        self._core = core
        self.engine.metrics = core.metrics
        self.engine._executor = core._executor
        self.engine.logger = core.logger
        self.engine._publish()

    async def execute_decoupled(
        self, inputs: Dict[str, np.ndarray], parameters: Dict[str, Any]
    ) -> AsyncIterator[Dict[str, np.ndarray]]:
        if "INPUT_IDS" not in inputs:
            raise InferenceServerException(
                f"model '{self.name}' expects input INPUT_IDS"
            )
        prompt = np.asarray(inputs["INPUT_IDS"], dtype=np.int32).reshape(-1)
        seq = self.engine.submit(prompt.tolist(), parameters=parameters)
        try:
            async for token, final in seq:
                yield {
                    "OUTPUT_IDS": np.array([token], dtype=np.int32),
                    "__final__": final,
                }
        finally:
            # client cancellation / stream teardown: the engine reclaims
            # the sequence's KV blocks within one step-loop iteration
            self.engine.release(seq)

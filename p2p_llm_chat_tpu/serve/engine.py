"""TPU inference engine: the real model behind the Ollama-compatible API.

This is the in-tree replacement for the reference's external Ollama server
(the one capability that defines the project — web/streamlit_app.py:91-98
delegates every suggestion to ``POST {OLLAMA_URL}/api/generate``; here the
same HTTP surface is backed by the JAX model stack on TPU).

Composition: :class:`TPUEngine` implements the serve ``Backend`` protocol
(serve/backend.py) over a :class:`~.scheduler.BatchScheduler`, which merges
all concurrent requests into one fixed-shape batched decode loop.

Two provisioning paths (build_engine_from_env):

- ``CKPT_DIR`` set: HF-layout safetensors checkpoint + its tokenizer.json
  (models/weights.py, tokenizer.py) — the production path for real llama3 /
  Mixtral weights.
- no checkpoint: randomly-initialised weights for ``MODEL_CONFIG`` (default
  ``tiny``) + the byte tokenizer, so the full serving stack runs with no
  artifacts — the same posture as FakeLLM, but exercising every real
  device code path.

The engine serves on the TPU. It boots on the CPU only when the
operator pins ``JAX_PLATFORMS=cpu`` (the test suite does); any other
non-TPU platform fails the boot (utils/device.require_tpu) instead of
serving from wherever JAX fell back to.

Env surface (reference-style env-first config, utils/env.py):
``SERVE_BACKEND=tpu``, ``CKPT_DIR``, ``MODEL_CONFIG``, ``SERVE_SLOTS``,
``SERVE_MAX_SEQ``, ``SERVE_TP``, ``LLM_MODEL`` (served model tag),
``SERVE_PAGE_SIZE``, ``SERVE_PAGES``,
``SERVE_ADMIT_CHUNK``, ``SERVE_QUEUE_TIMEOUT`` (seconds, 0 disables),
``SERVE_QUEUE_MAX`` (admission-queue depth bound for overload shedding:
unset = 8 x SERVE_SLOTS, 0 = unbounded; at the bound, submits fast-fail
with 503 + Retry-After instead of burning the queue deadline),
``SERVE_LOOP_BUDGET_MS`` (scheduler-loop watchdog budget; 0 disables),
``SERVE_QUANT`` (int8 = weight-only quantization, models/quant.py),
``SERVE_SPEC`` (K>0 = speculative decoding: hybrid prompt-lookup n-gram
drafts + the optional resident draft model),
``SERVE_DRAFT`` (draft-model config name or checkpoint dir, resident on
the same chip; drafts wherever the n-gram index misses — needs
SERVE_SPEC > 0; serve/draft_model.py),
``SERVE_FUSE`` (fused multi-step decode: up to K decode steps per device
dispatch, adaptive; default 4, 1 disables),
``SERVE_PREFILL_CHUNK`` (chunked prefill: admissions above this token
budget land in fixed chunks interleaved with decode ticks; default 256,
0 disables),
``SERVE_KV_HOST_GB`` (multi-tier KV: host-RAM session parking budget in
GB — finished conversations' KV stays open and follow-up turns wake it
instead of re-prefilling the history; 0 disables; serve/kv_tier.py),
``SERVE_KV_IDLE_S`` (seconds a resident session idles before parking
to host RAM),
``SERVE_PREFIX`` (shared-prefix KV caching, serve/prefix.py; default on),
``SERVE_PREFIX_TEXTS`` (extra templates to pre-register, ``||``-separated;
the reference co-pilot template is always registered),
``SERVE_MODELS`` (multi-model serving, serve/multi.py:
``tag=ref,...`` where ref is a config name OR a checkpoint directory —
one independent engine per tag with its own weights/tokenizer/KV pool,
requests route by their model field; a CKPT_DIR alongside becomes the
default entry under LLM_MODEL's tag).
"""

from __future__ import annotations

import threading
import os
from typing import Iterator, Optional

import jax

from ..models.configs import get_config
from ..models import family_for
from ..models.weights import load_checkpoint
from ..tokenizer import ByteTokenizer, load_tokenizer
from ..utils.device import bytes_in_use, device_info, require_tpu
from ..utils.env import env_bool, env_float, env_int, env_or
from ..utils.log import get_logger
from .backend import Backend, GenerateRequest, RequestStats
from .scheduler import BatchScheduler

log = get_logger("serve.engine")

# The head of the reference co-pilot's fixed prompt template
# (web/streamlit_app.py:93, reproduced byte-identically in ui.py
# SUGGEST_TEMPLATE) — every suggestion request starts with these bytes,
# so its KV is registered in the prefix cache up front.
SUGGEST_PREFIX = ("You are a helpful assistant. Draft a concise, friendly "
                  "reply to the following message:\n\n")


class TPUEngine:
    """Backend over the continuous-batching scheduler."""

    def __init__(self, params: dict, config, tokenizer, *,
                 num_slots: int = 8, max_seq: int = 1024, mesh=None,
                 name: Optional[str] = None, page_size: int = 64,
                 num_pages: Optional[int] = None,
                 admit_chunk: Optional[int] = None,
                 queue_timeout_s: Optional[float] = 60.0,
                 spec_k: int = 0,
                 prefix_cache: bool = True,
                 prefix_texts: tuple[str, ...] = (SUGGEST_PREFIX,),
                 kv_quant: bool = False,
                 decode_fuse_max: int = 4,
                 prefill_chunk: int = 256,
                 queue_max: Optional[int] = None,
                 draft: Optional[tuple] = None,
                 kv_host_gb: float = 0.0,
                 kv_idle_s: float = 30.0,
                 spec_tree_nodes: int = 0,
                 spec_tree_gap: float = 4.0) -> None:
        """``draft``: optional ``(params, config)`` of a small draft
        model made resident alongside this engine's target for
        speculative decoding (SERVE_DRAFT; serve/draft_model.py). Needs
        ``spec_k`` > 0, a matching vocabulary, and single-chip serving
        (mesh=None) — incompatible pairings log and fall back to
        n-gram-only speculation rather than failing the boot (a bad
        optimizer flag must not take the serving plane down)."""
        self.name = name or config.name
        self.config = config
        self.prefix_texts = tuple(prefix_texts) if prefix_cache else ()
        self._embed_j = None      # guarded-by: _embed_lock
        self._embed_lock = threading.Lock()
        drafter = None
        if draft is not None and spec_k:
            dparams, dconfig = draft
            if dconfig.vocab_size != config.vocab_size:
                log.warning(
                    "SERVE_DRAFT model %s (vocab %d) cannot draft for "
                    "%s (vocab %d); falling back to n-gram-only "
                    "speculation", dconfig.name, dconfig.vocab_size,
                    config.name, config.vocab_size)
            elif mesh is not None:
                log.warning("SERVE_DRAFT is single-chip only (the "
                            "drafter does not shard); falling back to "
                            "n-gram-only speculation under a mesh")
            elif (min(max_seq, dconfig.max_seq_len)
                  < min(max_seq, config.max_seq_len)):
                # The scheduler hard-raises on a drafter that cannot
                # cover the target's context budget — catch it here so
                # a bad flag degrades instead of failing the boot.
                log.warning(
                    "SERVE_DRAFT model %s (max_seq_len %d) cannot cover "
                    "the serving budget %d; falling back to n-gram-only "
                    "speculation", dconfig.name, dconfig.max_seq_len,
                    min(max_seq, config.max_seq_len))
            else:
                from .draft_model import ModelDrafter
                drafter = ModelDrafter(dparams, dconfig,
                                       num_slots=num_slots,
                                       max_seq=max_seq, k=spec_k)
                # Second-model memory accounting: the drafter's params
                # + dense KV are a fixed add-on the operator budgets
                # against HBM next to the target's pool.
                log.info(
                    "draft model resident: %s (%.2f GB params, "
                    "%.2f GB KV at %d slots x %d) drafting k=%d for %s",
                    dconfig.name, drafter.param_bytes() / 1e9,
                    drafter.kv_bytes() / 1e9, num_slots,
                    drafter.max_seq, spec_k, config.name)
        self.scheduler = BatchScheduler(params, config, tokenizer,
                                        num_slots=num_slots, max_seq=max_seq,
                                        mesh=mesh, page_size=page_size,
                                        num_pages=num_pages,
                                        admit_chunk=admit_chunk,
                                        queue_timeout_s=queue_timeout_s,
                                        spec_k=spec_k,
                                        prefix_cache=prefix_cache,
                                        kv_quant=kv_quant,
                                        decode_fuse_max=decode_fuse_max,
                                        prefill_chunk=prefill_chunk,
                                        queue_max=queue_max,
                                        drafter=drafter,
                                        kv_host_gb=kv_host_gb,
                                        kv_idle_s=kv_idle_s,
                                        spec_tree_nodes=spec_tree_nodes,
                                        spec_tree_gap=spec_tree_gap)

    def generate_stream(self, req: GenerateRequest,
                        stats: Optional[RequestStats] = None) -> Iterator[str]:
        return self.scheduler.submit(req, stats)

    def render_chat(self, messages: list[dict]) -> str:
        """/api/chat prompt rendering. With a real llama3 tokenizer
        (header/eot specials present — the instruct checkpoints' chat
        format), messages render in the llama3 chat template, so a served
        instruct model sees exactly the turn structure it was trained on;
        BOS is added at encode time (scheduler tokenizes with
        add_bos=True), so it is not part of the template. Tokenizers
        without the specials (ByteTokenizer, non-llama vocabularies) get
        the model-agnostic role flattening."""
        tok = self.scheduler.tokenizer
        has = getattr(tok, "has_special", None)
        if not (callable(has) and has("<|start_header_id|>")
                and has("<|eot_id|>")):
            from .api import default_chat_prompt
            return default_chat_prompt(messages)
        # Message content/roles are untrusted: encode() maps special
        # strings anywhere in text to control ids, so specials embedded
        # in a message could forge turn structure (a fabricated system
        # turn). Strip them; only the template's own specials survive.
        clean = tok.strip_specials
        parts = []
        for m in messages:
            role = clean(str(m.get("role", "user")))
            parts.append(f"<|start_header_id|>{role}<|end_header_id|>\n\n"
                         f"{clean(str(m.get('content', '')))}<|eot_id|>")
        parts.append("<|start_header_id|>assistant<|end_header_id|>\n\n")
        return "".join(parts)

    def embed(self, texts: list[str]) -> tuple[list[list[float]], int]:
        """Sequence embeddings for Ollama's /api/embed[dings]: length-
        masked mean pool of final-norm hidden states, unit-normalized
        (models/llama.embed_pooled; the MoE family routes through its own
        expert MLP). Returns (vectors, total prompt tokens).

        Runs outside the scheduler loop on purpose: it reads only the
        (immutable) params — none of the scheduler-owned KV/sampling
        state — so it cannot race the decode loop; the lock bounds
        concurrent embed dispatches to one. Shapes are bucketed
        (power-of-two rows and length) so repeat calls hit the jit cache."""
        import numpy as np
        import jax.numpy as jnp

        sched = self.scheduler
        model = sched._model
        ids = [sched.tokenizer.encode(t, add_bos=True)[: sched.max_seq]
               for t in texts]
        n_tokens = sum(len(i) for i in ids)
        out: list[list[float]] = []
        from .scheduler import _bucket
        with self._embed_lock:
            if self._embed_j is None:     # under the lock: one wrapper,
                import functools          # one compile cache

                self._embed_j = jax.jit(functools.partial(
                    model.embed_pooled, config=self.config, mesh=sched.mesh))
            for start in range(0, len(ids), 16):    # bounded batch rows
                chunk = ids[start: start + 16]
                R = max(2, 1 << (len(chunk) - 1).bit_length())
                S = _bucket(max(len(i) for i in chunk), sched.max_seq)
                toks = np.zeros((R, S), np.int32)
                lens = np.ones((R,), np.int32)
                for r, seq in enumerate(chunk):
                    toks[r, : len(seq)] = seq
                    lens[r] = max(1, len(seq))
                # graftcheck: sync-ok,block-ok embed responses need the vectors now; the lock exists to serialize device embeds, the sync IS the guarded work
                vecs = np.asarray(self._embed_j(
                    sched._params, tokens=jnp.asarray(toks),
                    lens=jnp.asarray(lens)))
                # graftcheck: sync-ok,block-ok host numpy rows, already materialized above
                out.extend(vecs[r].tolist() for r in range(len(chunk)))
        return out, n_tokens

    def warmup(self, buckets: tuple[int, ...] = (128, 256),
               background: bool = False) -> None:
        """Compile the serving programs (admit per chunk-size x prompt
        bucket, decode per attention window) before real traffic arrives —
        first-compile on TPU is tens of seconds, which would otherwise land
        on the first users' TTFT. Also registers the known prompt-template
        prefixes so their KV and admission programs are ready."""
        def _run() -> None:
            try:
                self.scheduler.warmup(prompt_buckets=buckets,
                                      prefix_texts=self.prefix_texts)
            except Exception:   # noqa: BLE001 — recorded, see failed()
                log.exception("warmup failed — this engine will never "
                              "report ready")

        if background:
            # Not-ready from THIS call, not from when the thread gets
            # scheduled: a /readyz poll racing the spawn must never see
            # a ready engine whose warmup is about to start.
            self.scheduler.note_warmup_pending()
            threading.Thread(target=_run, daemon=True, name="warmup").start()
        else:
            _run()

    def models(self) -> list[str]:
        return [self.name]

    def ready(self) -> bool:
        """Readiness for /readyz: the scheduler loop is live and any
        started warmup has completed (background warmup is the default
        boot path — routing traffic mid-warmup lands compiles on real
        requests' TTFT)."""
        return self.scheduler.ready

    def failed(self) -> Optional[str]:
        """Terminal failure for /readyz and the process entry point: the
        warmup raised (a program the compiler refused, a dead device),
        so this engine can never go ready. serve/api.py answers 500 on
        /readyz and exits non-zero — a launcher must see a dead child,
        not poll a 503-warming server until its deadline."""
        return self.scheduler.warmup_error

    def metrics_snapshot(self) -> dict[str, float]:
        """Serving-plane gauges (batch occupancy, queue depth, KV pool)
        merged into the API front's /metrics (serve/api.py), plus the
        device this process computes on — the only place an operator
        (or chip_smoke.py) can read which platform actually serves."""
        out = self.scheduler.metrics_snapshot()
        info = device_info()
        out[f'serve_device_info{{platform="{info["platform"]}",'
            f'device_kind="{info["device_kind"]}",'
            f'count="{info["count"]}"}}'] = 1
        for i, n in enumerate(bytes_in_use()):
            out[f'serve_device_bytes{{device="{i}"}}'] = n
        return out

    # -- grafttrace (obs/, round 15) -----------------------------------------

    def set_trace_store(self, store) -> None:
        """The API front injects its span store so the scheduler's
        queue-wait/prefill/wake/decode spans land beside the front's
        own api.request span under one trace id."""
        self.scheduler.set_trace_store(store)

    def flight_snapshot(self) -> list:
        return self.scheduler.flight_snapshot()

    def flight_dump(self, reason: str = "on_demand") -> str:
        return self.scheduler.flight_dump(reason)

    # -- cross-replica shared prefix tier (serve/prefix.py round 11) ---------

    def prefix_hashes(self):
        """{token_hash: {len, hits}} of cached prefixes, or None when
        the prefix cache is off (the front answers 501), or when the
        entries cannot travel (below), so that no router asks."""
        store = self.scheduler._prefix
        if store is None or self.config.state_layers:
            return None
        return store.hashes()

    def _refuse_prefix_wire(self) -> None:
        """The wire format carries K and V alone. An entry of a model
        with recurrent state or window rings is its pages AND a state
        snapshot; one without the other would serve wrong answers, so
        this family's entries neither leave nor enter."""
        if self.config.state_layers:
            raise ValueError(
                f"{self.config.name} keeps {self.config.state_kinds} beside "
                "its pages: its prefix entries are not exported or imported "
                "over /admin/prefix")

    def prefix_export(self, h: str):
        self._refuse_prefix_wire()
        store = self.scheduler._prefix
        return None if store is None else store.export_payload(h)

    def prefix_import(self, data: bytes):
        """Install a peer replica's exported prefix entry (thread-safe:
        the store locks; the scheduler reads entries between admission
        dispatches). Admission programs for grain-snapped imports are
        covered by warmup's grain pre-warm."""
        self._refuse_prefix_wire()
        store = self.scheduler._prefix
        return None if store is None else store.import_payload(data)

    # -- live session migration (serve/kv_tier.py round 13) ------------------
    # The router composes these over /admin/session: park-all on the
    # source, pull payloads to the destination, forget on ack — so a
    # drain is a migration and a dead replica costs a bounded cold
    # re-prefill, never a client error.

    def session_list(self):
        return self.scheduler.session_list()

    def session_export(self, key: str):
        return self.scheduler.session_export(key)

    def session_import(self, data: bytes):
        return self.scheduler.session_import(data)

    def session_forget(self, key: str):
        return self.scheduler.session_forget(key)

    def session_park_all(self) -> None:
        self.scheduler.park_all()

    def prefill_park(self, req: GenerateRequest):
        """Disaggregated serving (serve/disagg.py round 14): run this
        request's chunked prefill to completion and retain the KV as an
        exportable session — the decode replica pulls it and samples
        the first token there. None = not parkable (the router routes
        the request un-disaggregated)."""
        return self.scheduler.prefill_park(req)

    def drain(self) -> None:
        """Replica drain hook (serve/router.py): finish in-flight
        streams, refuse new sessions, report not-ready on /readyz."""
        self.scheduler.drain()

    def undrain(self) -> None:
        self.scheduler.undrain()

    def draining(self) -> bool:
        return self.scheduler.draining

    def stop(self) -> None:
        self.scheduler.stop()


def log_device(after: str) -> None:
    """The boot line that names the device: platform, kind, count and
    allocated bytes per device (weights + KV pool after a load)."""
    info = device_info()
    log.info("device: platform=%s kind=%r count=%d bytes_in_use=%s (%s)",
             info["platform"], info["device_kind"], info["count"],
             bytes_in_use(), after)


def build_engine_from_env() -> Backend:
    """Engine from env vars; a random tiny model + byte tokenizer when
    no checkpoint is configured.

    ``SERVE_COORDINATOR`` (or the JAX_COORDINATOR/... trio) switches to
    the multi-host SPMD engine: every process joins the distributed
    runtime and shards the model over the hybrid dp-over-DCN mesh;
    process 0 serves HTTP, the rest mirror its programs
    (serve/multihost.py — api.main() dispatches follower_loop)."""
    from ..obs.phase import compile_clock
    from ..utils.jax_cache import enable_persistent_cache
    # Before the first compile: the streamed init's programs count
    # towards serve_boot_compile_seconds too.
    compile_clock()
    enable_persistent_cache()
    coord = env_or("SERVE_COORDINATOR", "") or None
    if coord or env_or("JAX_COORDINATOR", ""):
        from .multihost import build_multihost_engine
        return build_multihost_engine(coord)
    require_tpu("SERVE_BACKEND=tpu")
    ckpt_dir = env_or("CKPT_DIR", "")
    num_slots = env_int("SERVE_SLOTS", 8)
    max_seq = env_int("SERVE_MAX_SEQ", 1024)
    tp = env_int("SERVE_TP", 1)
    # Input validation only: the paged pool is the one KV layout, and
    # deployments still export SERVE_KV=paged.
    serve_kv = env_or("SERVE_KV", "paged")
    if serve_kv != "paged":
        raise SystemExit(
            f"SERVE_KV={serve_kv!r}: dense serving was removed in PR 28; "
            "the paged pool is the only KV layout (unset SERVE_KV, or set "
            "it to paged)")
    page_size = env_int("SERVE_PAGE_SIZE", 64)
    num_pages = env_int("SERVE_PAGES", 0) or None
    admit_chunk = env_int("SERVE_ADMIT_CHUNK", 0) or None
    # Admission deadline (seconds; 0 disables). Default mirrors the
    # reference client's 60 s LLM timeout (web/streamlit_app.py:95).
    qt = float(env_or("SERVE_QUEUE_TIMEOUT", "60"))
    queue_timeout_s = qt if qt > 0 else None
    # Overload shedding: admission-queue depth bound. Unset = auto
    # (8 x SERVE_SLOTS — see scheduler.queue_max); 0 = unbounded legacy
    # queue (requests at capacity burn the deadline instead of a fast
    # 503 + Retry-After).
    qm = env_int("SERVE_QUEUE_MAX", -1)
    queue_max = None if qm < 0 else qm
    spec_k = env_int("SERVE_SPEC", 0)
    # Draft-model speculative decoding (serve/draft_model.py): a config
    # name (random-init / synthetic path — CPU tests) or a
    # checkpoint dir (the production path: e.g. a llama3.2-1b instruct
    # checkpoint drafting for llama3.1-8b) of a SMALL model resident
    # alongside the target. Requires SERVE_SPEC > 0; drafts fill in
    # wherever the n-gram index misses, so speculation wins on free-form
    # output, not just quoting.
    draft_ref = env_or("SERVE_DRAFT", "")
    if draft_ref and not spec_k:
        log.warning("SERVE_DRAFT set but SERVE_SPEC=0 — no speculative "
                    "ticks will run; set SERVE_SPEC (e.g. 4) to enable "
                    "the drafter")
    # Tree speculation (round 17): widen the verify window from K+1 to
    # this many node positions (pow2-snapped; needs >= spec_k+2 for a
    # sibling slot, else the scheduler degrades to linear spec). Only
    # engages when SERVE_SPEC > 0. SERVE_SPEC_TREE_GAP is the top-1/
    # top-2 drafter logit gap below which a position gets a sibling.
    spec_tree_nodes = env_int("SERVE_SPEC_TREE_NODES", 8) if spec_k else 0
    spec_tree_gap = env_float("SERVE_SPEC_TREE_GAP", 4.0)
    # Fused multi-step decode: up to this many decode steps per device
    # dispatch (adaptive — see scheduler.decode_fuse_max). 1 disables.
    decode_fuse_max = max(1, env_int("SERVE_FUSE", 4))
    # Chunked prefill: admissions whose bucket exceeds this token budget
    # land in fixed chunks interleaved with decode ticks (Sarathi-style
    # stall-free admission — see scheduler.prefill_chunk). 0 disables
    # (legacy whole-bucket admission).
    prefill_chunk = max(0, env_int("SERVE_PREFILL_CHUNK", 256))
    # Multi-tier KV (serve/kv_tier.py): host-RAM session parking. > 0
    # enables — finished conversations' KV stays open (resident pages
    # first, host-RAM copies under idle/pressure) up to this many GB of
    # host RAM, and follow-up turns wake instead of re-prefilling.
    kv_host_gb = env_float("SERVE_KV_HOST_GB", 0.0)
    kv_idle_s = env_float("SERVE_KV_IDLE_S", 30.0)
    prefix_cache = env_bool("SERVE_PREFIX", True)
    prefix_texts = (SUGGEST_PREFIX,) + tuple(
        t for t in env_or("SERVE_PREFIX_TEXTS", "").split("||") if t)
    # SERVE_PROFILE_PORT=N starts jax.profiler's collection server:
    # attach TensorBoard/xprof to capture live device traces of the
    # serving loop (SURVEY.md §5 tracing plan).
    prof_port = env_int("SERVE_PROFILE_PORT", 0)
    if prof_port:
        jax.profiler.start_server(prof_port)
        log.info("jax.profiler server on :%d", prof_port)

    mesh = None
    n_dev = len(jax.devices())
    if tp > 1:
        from ..parallel.mesh import local_mesh
        mesh = local_mesh(tp=tp)
        if n_dev > tp:
            log.warning("SERVE_TP=%d on %d devices: the mesh adds a dp axis "
                        "of %d over the rest (weights replicated across "
                        "it)", tp, n_dev, n_dev // tp)
    elif n_dev > 1:
        log.warning("%d devices are visible and SERVE_TP is unset: the "
                    "model and its KV pool live on device 0 alone", n_dev)

    quant = env_or("SERVE_QUANT", "")
    if quant not in ("", "int8", "int4"):
        raise SystemExit(
            f"SERVE_QUANT must be one of '', 'int8', 'int4'; "
            f"got {quant!r}")
    kv_quant = env_or("SERVE_KV_QUANT", "")
    if kv_quant and kv_quant != "int8":
        raise SystemExit(
            f"SERVE_KV_QUANT must be int8 or empty, got {kv_quant!r}")

    def random_init_params(config, seed: int):
        """Shared per-model build: random init -> shard -> quantize.
        Single-chip quantized llama-family configs stream straight to
        the fused int8/int4 tree (never materialising the bf16 tree) so
        MODEL_CONFIG=llama3.1-8b serves on one 16 GB chip."""
        family = family_for(config)
        if (quant and mesh is None
                and hasattr(family, "init_params_quantized")):
            return family.init_params_quantized(config,
                                                jax.random.PRNGKey(seed),
                                                quant=quant)
        if mesh is not None:
            # Generate every leaf straight into its shards: the eager
            # init materialises whole f32 leaves on device 0 (7.5 GB for
            # one 8B MLP stack) before anything is sharded.
            from jax.sharding import NamedSharding, PartitionSpec
            from ..parallel.sharding import tree_specs
            shardings = jax.tree.map(
                lambda spec: NamedSharding(mesh, spec),
                tree_specs(family.param_axes(config)),
                is_leaf=lambda x: isinstance(x, PartitionSpec))
            params = jax.jit(lambda k: family.init_params(config, k),
                             out_shardings=shardings)(
                                 jax.random.PRNGKey(seed))
        else:
            params = family.init_params(config, jax.random.PRNGKey(seed))
        if quant:
            from ..models.quant import quantize_params
            params = quantize_params(params, mesh=mesh, mode=quant)
        return params

    def load_draft_for(config) -> Optional[tuple]:
        """(params, config) for SERVE_DRAFT against this target, or
        None. A directory loads the checkpoint (strict vocabulary — the
        engine falls back with a warning on mismatch); a config name
        random-inits at the TARGET's vocabulary (random weights carry
        no vocabulary semantics, so cloning the config at the right
        vocab keeps the no-checkpoint path drafting end to end)."""
        if not draft_ref or not spec_k:
            return None
        if mesh is not None:
            log.warning("SERVE_DRAFT is single-chip only (the drafter "
                        "does not shard); ignoring it under SERVE_TP>1 "
                        "— n-gram-only speculation")
            return None
        if os.sep in draft_ref or os.path.isdir(draft_ref):
            # Same format probe as the target path (native orbax vs HF
            # safetensors); any load failure degrades to n-gram-only —
            # the drafter is an optimizer, it must not take serving down.
            try:
                from ..models.checkpoint import is_native_checkpoint
                if is_native_checkpoint(draft_ref):
                    from ..models.checkpoint import \
                        load_checkpoint as load_native
                    dparams, dconfig = load_native(draft_ref)
                else:
                    dparams, dconfig = load_checkpoint(draft_ref)
                if quant:
                    from ..models.quant import quantize_params
                    dparams = quantize_params(dparams, mode=quant)
            except Exception:   # noqa: BLE001 — degrade, don't fail boot
                log.exception(
                    "SERVE_DRAFT checkpoint %r failed to load; falling "
                    "back to n-gram-only speculation", draft_ref)
                return None
            return dparams, dconfig
        try:
            dconfig = get_config(draft_ref)
        except KeyError:
            log.warning("SERVE_DRAFT %r is neither a checkpoint dir nor "
                        "a registered config; falling back to n-gram-only "
                        "speculation", draft_ref)
            return None
        if dconfig.vocab_size != config.vocab_size:
            dconfig = dconfig.with_(vocab_size=config.vocab_size)
        return random_init_params(dconfig, 101), dconfig

    def make_engine(params, config, tokenizer, name: str) -> TPUEngine:
        return TPUEngine(params, config, tokenizer, num_slots=num_slots,
                         max_seq=max_seq, mesh=mesh, page_size=page_size,
                         num_pages=num_pages,
                         admit_chunk=admit_chunk,
                         queue_timeout_s=queue_timeout_s, spec_k=spec_k,
                         prefix_cache=prefix_cache,
                         prefix_texts=prefix_texts, name=name,
                         kv_quant=bool(kv_quant),
                         decode_fuse_max=decode_fuse_max,
                         prefill_chunk=prefill_chunk,
                         queue_max=queue_max,
                         draft=load_draft_for(config),
                         kv_host_gb=kv_host_gb, kv_idle_s=kv_idle_s,
                         spec_tree_nodes=spec_tree_nodes,
                         spec_tree_gap=spec_tree_gap)

    def warmup_buckets():
        warmup = env_or("SERVE_WARMUP", "128,256")
        if not warmup or warmup == "0":
            return None
        return tuple(int(b) for b in warmup.split(",") if b.strip())

    def load_ckpt_engine(tag: Optional[str], path: str) -> TPUEngine:
        """One fully-independent engine from a checkpoint dir: its own
        params, its own tokenizer, its own scheduler/KV pool — engines
        share nothing but the HTTP front. The single-model CKPT_DIR path
        uses this too (tag=None names the engine LLM_MODEL/config.name),
        so the format probe and quantization cannot drift between the
        single- and multi-model paths."""
        from ..models.checkpoint import is_native_checkpoint
        already_quantized = False
        if quant and mesh is None:
            # Single-chip quantized: stream straight into the fused
            # int8/int4 tree so the bf16 model never touches the chip
            # (what fits an 8B checkpoint on one 16 GB v5e). Llama and
            # mixtral families; anything else falls through to the
            # standard paths.
            from ..models.weights import (
                UnsupportedForQuantizedLoad,
                load_checkpoint_quantized,
            )
            try:
                params, config = load_checkpoint_quantized(path,
                                                           quant=quant)
                already_quantized = True
            except UnsupportedForQuantizedLoad:
                # Family out of scope (MoE etc.) — standard paths below.
                # Real load errors (corrupt shards) must PROPAGATE: the
                # fallback would re-materialise the bf16 tree and OOM big
                # models with a misleading error.
                params = None
        else:
            params = None
        if params is None:
            if is_native_checkpoint(path):
                from ..models.checkpoint import load_checkpoint as load_native
                params, config = load_native(path, mesh=mesh)
            elif mesh is not None:
                # Mesh loads are the big-model path: stream tensors
                # straight into the sharded device tree so host RAM never
                # holds the checkpoint (the 70B memory-fit requirement).
                from ..models.weights import load_checkpoint_streaming
                params, config = load_checkpoint_streaming(path, mesh=mesh)
            else:
                params, config = load_checkpoint(path, mesh=mesh)
        tokenizer = load_tokenizer(path, vocab_size=config.vocab_size)
        if quant and not already_quantized:
            from ..models.quant import quantize_params
            params = quantize_params(params, mesh=mesh, mode=quant)
            log.info("weights quantized to %s (%s)", quant,
                     "per-channel, w8a16" if quant == "int8"
                     else "group-wise, w4a16")
        return make_engine(params, config, tokenizer,
                           name=tag or env_or("LLM_MODEL", config.name))

    # Multi-model serving (serve/multi.py): SERVE_MODELS=tag=ref,...
    # builds one independent engine per tag behind one front; requests
    # route by their model field. A ref is a registered config name
    # (random-init, byte tokenizer — the routing-demo path) or a
    # checkpoint directory (real weights + its own tokenizer). CKPT_DIR
    # composes: it becomes the default entry under LLM_MODEL's tag.
    models_spec = env_or("SERVE_MODELS", "")
    if models_spec:
        from .multi import MultiBackend
        # Validate the whole spec BEFORE building anything: each engine
        # starts a live scheduler thread, so a bad later entry must not
        # leak earlier ones (and a duplicate tag must not silently drop
        # a fully-started engine).
        specs: list[tuple[str, str]] = []
        if ckpt_dir:
            specs.append((env_or("LLM_MODEL", "default"), ckpt_dir))
        for part in models_spec.split(","):
            part = part.strip()
            if not part:
                continue
            tag, _, ref = part.partition("=")
            if not tag:
                raise SystemExit(f"SERVE_MODELS entry {part!r} has an "
                                 "empty tag")
            if any(t == tag for t, _ in specs):
                raise SystemExit(f"SERVE_MODELS has duplicate tag {tag!r}")
            specs.append((tag, ref or tag))
        def is_ckpt_ref(ref: str) -> bool:
            """A ref is a checkpoint dir only when it LOOKS like a path
            (contains a separator) or is not a registered config name —
            a bare config name that happens to collide with a directory
            in the CWD (e.g. ./tiny) must still serve the config."""
            if os.sep in ref:
                return True
            if ref in __import__(
                    "p2p_llm_chat_tpu.models.configs",
                    fromlist=["CONFIGS"]).CONFIGS:
                return False
            return os.path.isdir(ref)

        for tag, ref in specs:
            if is_ckpt_ref(ref):
                if not os.path.isdir(ref):
                    raise SystemExit(
                        f"SERVE_MODELS entry {tag}={ref}: no such "
                        "checkpoint directory")
            else:
                try:
                    get_config(ref)
                except KeyError as e:
                    raise SystemExit(f"SERVE_MODELS entry {tag}={ref}: "
                                     f"{e}") from None
        backends: dict = {}
        for i, (tag, ref) in enumerate(specs):
            if is_ckpt_ref(ref):
                backends[tag] = load_ckpt_engine(tag, ref)
            else:
                config = get_config(ref)
                tokenizer = ByteTokenizer(vocab_size=config.vocab_size)
                backends[tag] = make_engine(random_init_params(config, i),
                                            config, tokenizer, name=tag)
        multi = MultiBackend(backends, default=specs[0][0])
        log.info("multi-model serving: %s", ", ".join(multi.models()))
        log_device("weights and KV pools loaded")
        buckets = warmup_buckets()
        if buckets:
            multi.warmup(buckets, background=True)
        return multi

    if ckpt_dir:
        engine = load_ckpt_engine(None, ckpt_dir)
    else:
        config = get_config(env_or("MODEL_CONFIG", "tiny"))
        log.info("no CKPT_DIR set: serving random-init %s with byte tokenizer",
                 config.name)
        params = random_init_params(config, 0)
        if quant:
            log.info("weights quantized to %s (%s)", quant,
                     "per-channel, w8a16" if quant == "int8"
                     else "group-wise, w4a16")
        tokenizer = ByteTokenizer(vocab_size=config.vocab_size)
        engine = make_engine(params, config, tokenizer,
                             name=env_or("LLM_MODEL", config.name))
    log_device("weights and KV pool loaded")
    buckets = warmup_buckets()
    if buckets:
        engine.warmup(buckets, background=True)
    return engine

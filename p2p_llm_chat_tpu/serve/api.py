"""Ollama-compatible HTTP front for the TPU serving stack.

The drop-in replacement for the reference's external Ollama server: the UI's
``OLLAMA_URL`` points here unchanged. Contract (from web/streamlit_app.py:
91-98 and BASELINE.json's north star — both endpoints implemented, see
SURVEY.md §1 L4 note):

- ``POST /api/generate``  body ``{"model", "prompt", "stream", "options",
  "context"}``; non-streaming response carries ``{"response": ...,
  "done": true}`` plus Ollama's timing fields and the updated ``context``
  ids (stateless continuation — send them back to continue the exchange);
  streaming (Ollama's default when ``stream`` is omitted) sends NDJSON
  chunks ``{"response": <delta>, "done": false}`` and a final
  ``done: true`` record with stats.
- ``POST /api/chat``      same shapes with ``messages`` / ``message``.
- ``POST /api/embed``     sequence embeddings (``input``: str | [str]);
  ``POST /api/embeddings`` is the legacy single-prompt form.
- ``GET  /api/tags``      model listing.
- ``GET  /api/version``, ``GET /`` ("Ollama is running") — client health
  checks.
- ``GET  /metrics``       Prometheus-style counters: request counts, TTFT
  and total-latency summaries, tokens generated, in-flight gauge (the
  benchmark metrics of PERF.md, in-tree per SURVEY.md §5).
"""

from __future__ import annotations

import json
import threading
import time
from typing import Iterator, Optional

from ..obs import trace as _trace
from ..proto import now_rfc3339
from ..utils import backoff as _backoff
from ..utils import failpoints as _failpoints
from ..utils.env import env_int, env_or
from ..utils.failpoints import failpoint
from ..utils.http import HttpServer, Request, Response, Router
from ..utils.log import get_logger
from ..utils.metrics import Registry
from .backend import (Backend, GenerateOptions, GenerateRequest,
                      OverloadError, RequestStats)

log = get_logger("serve.api")


def default_chat_prompt(messages: list[dict]) -> str:
    """Model-agnostic flattening of an /api/chat messages list."""
    parts = []
    for m in messages:
        role = m.get("role", "user")
        parts.append(f"{role}: {m.get('content', '')}")
    parts.append("assistant:")
    return "\n".join(parts)


def render_chat_prompt(messages: list[dict], backend: Backend) -> str:
    """Flatten an /api/chat messages list into a prompt. Backends that have a
    tokenizer-aware chat template override via ``render_chat``."""
    fn = getattr(backend, "render_chat", None)
    if fn is not None:
        return fn(messages)
    return default_chat_prompt(messages)


class OllamaServer:
    def __init__(self, backend: Backend, addr: Optional[str] = None,
                 registry: Optional[Registry] = None,
                 replica_class: Optional[str] = None) -> None:
        self.backend = backend
        # Disaggregated serving (serve/disagg.py round 14): this
        # replica's declared role, advertised on /readyz and /metrics
        # so the router's scrape loop sorts it into the right pool.
        from .disagg import REPLICA_CLASSES, replica_class_from_env
        self.replica_class = (replica_class if replica_class is not None
                              else replica_class_from_env())
        if self.replica_class not in REPLICA_CLASSES:
            raise ValueError(f"replica_class must be one of "
                             f"{REPLICA_CLASSES}, got "
                             f"{self.replica_class!r}")
        # Eager FAIL_POINTS parse: a malformed chaos config must fail
        # HERE, at boot, not as a ValueError at some arbitrary deep
        # failpoint() mid-serving.
        _failpoints.load_env()
        # 11434 is Ollama's default port; SERVE_ADDR overrides.
        self.addr_cfg = addr if addr is not None else env_or("SERVE_ADDR", "127.0.0.1:11434")
        # /api/generate echoes a finished exchange's token ids as
        # ``context`` for a follow-up to send back; SERVE_CONTEXT_MAX > 0
        # leaves the echo out of an exchange longer than that many ids.
        # A STOP-GAP for the benchmark's client, not an operator's option
        # (a deployment runs 0): a 15 K-token document comes back as a
        # 70 KB line and benchmark/loadgen.py reads lines of 64 KiB. It
        # goes, with _echoes, when the client's limit is raised
        # (ROADMAP S1b(13); PERF.md section 7(xx)).
        self._context_max = env_int("SERVE_CONTEXT_MAX", 0)
        self.metrics = registry or Registry()
        self._m_requests = self.metrics.counter("serve_requests_total")
        self._m_errors = self.metrics.counter("serve_errors_total")
        # HTTP-plane view of overload shedding (the scheduler's own
        # requests_shed_total arrives via the backend snapshot): how many
        # 503s THIS front returned.
        self._m_shed = self.metrics.counter("serve_requests_shed_total")
        self._m_tokens = self.metrics.counter("serve_completion_tokens_total")
        self._m_inflight = self.metrics.gauge("serve_inflight_requests")
        self._m_ttft = self.metrics.histogram("serve_ttft_seconds")
        self._m_total = self.metrics.histogram("serve_request_seconds")
        self.router = Router()
        self.router.add("POST", "/api/generate", self._generate)
        self.router.add("POST", "/api/chat", self._chat)
        self.router.add("GET", "/api/tags", self._tags)
        self.router.add("POST", "/api/show", self._show)
        self.router.add("GET", "/api/ps", self._ps)
        self.router.add("POST", "/api/embed", self._embed)
        self.router.add("POST", "/api/embeddings", self._embeddings_legacy)
        # Model-management endpoints (pull/push/create/copy/delete) exist
        # in Ollama to mutate its local model store; here models are
        # provisioned from checkpoints at startup (CKPT_DIR), so these
        # answer with an explicit 501 instead of a confusing 404 — Ollama
        # clients get a clear, parseable error record.
        for ep in ("/api/pull", "/api/push", "/api/create", "/api/copy"):
            self.router.add("POST", ep, self._unsupported)
        self.router.add("DELETE", "/api/delete", self._unsupported)
        self.router.add("GET", "/api/version", lambda r: Response(200, {
            "version": "0.1.0-p2p-llm-chat-tpu"}))
        self.router.add("GET", "/", lambda r: Response(
            200, "Ollama is running", content_type="text/plain"))
        self.router.add("HEAD", "/", lambda r: Response(200, ""))
        self.router.add("GET", "/metrics", self._metrics)
        # Liveness vs readiness are DISTINCT probes: /healthz answers
        # "is the process up" (static 200 — a restart won't fix a
        # warming server, so an orchestrator must not kill it for being
        # slow to compile), while /readyz answers "should a load
        # balancer route traffic here" (503 until the backend's warmup
        # completes — routing earlier puts tens-of-seconds compiles on
        # real requests' TTFT).
        self.router.add("GET", "/healthz", lambda r: Response(200, {"status": "ok"}))
        self.router.add("GET", "/readyz", self._readyz)
        # Drain hooks (replica-router mode, serve/router.py): draining
        # finishes in-flight streams but refuses new sessions and flips
        # /readyz, so a balancer retires this replica gracefully.
        # Front-level flag covers backends without their own drain()
        # (FakeLLM); engine backends ALSO drain their scheduler so
        # direct submits shed too.
        self._draining = threading.Event()
        self.router.add("POST", "/admin/drain", self._drain)
        self.router.add("POST", "/admin/undrain", self._undrain)
        # Cross-replica shared prefix tier (serve/prefix.py round 11):
        # the router lists each replica's cached prefixes by token hash
        # and tells replicas missing a hot one to pull it from the
        # replica that built it — control messages through the router,
        # KV bytes replica-to-replica.
        self.router.add("GET", "/admin/prefix", self._prefix_list)
        self.router.add("GET", "/admin/prefix/export", self._prefix_export)
        self.router.add("POST", "/admin/prefix/import", self._prefix_import)
        # Live session migration (serve/kv_tier.py round 13): parked
        # sessions serialize replica-to-replica exactly like prefix
        # entries — the router drives drain-as-migration and failure
        # rehoming over these; KV bytes never pass through the router.
        self.router.add("GET", "/admin/session", self._session_list)
        self.router.add("GET", "/admin/session/export", self._session_export)
        self.router.add("POST", "/admin/session/import", self._session_import)
        self.router.add("POST", "/admin/session/forget", self._session_forget)
        self.router.add("POST", "/admin/session/park_all",
                        self._session_park_all)
        # Disaggregated prefill (serve/disagg.py round 14): the router
        # sends a NEW conversation's request here on a prefill-class
        # replica; the backend chunk-prefills it to a parked session a
        # decode replica then pulls over /admin/session.
        self.router.add("POST", "/admin/disagg/prefill",
                        self._disagg_prefill)
        # grafttrace (obs/, round 15): this replica's bounded span
        # store, injected into the backend so scheduler-side spans land
        # under the same trace ids the wire header carries. bind_registry
        # is THE registration site for the serve_trace_* series.
        self.trace = _trace.TraceStore()
        self.trace.bind_registry(self.metrics)
        set_store = getattr(backend, "set_trace_store", None)
        if callable(set_store):
            set_store(self.trace)
        self.router.add("GET", "/admin/trace", self._trace_list)
        self.router.add("POST", "/admin/trace/dump", self._trace_dump)
        self._server: Optional[HttpServer] = None

    # -- helpers -------------------------------------------------------------

    def _readyz(self, req: Request) -> Response:
        """Readiness: backends exposing ``ready()`` (the TPU engine —
        warmup-gated; multi-model fronts AND their engines) gate the
        answer; backends without it (FakeLLM) are ready when live.
        Draining (the replica-router retire path) is not-ready with its
        own status so an operator can tell it from warming."""
        cls = self.replica_class
        err = self._backend_failed()
        if err:
            # Terminal (a warmup the compiler refused): 500, no
            # Retry-After — polling will not help; serve_forever exits.
            return Response(500, {"status": "failed", "error": err,
                                  "class": cls})
        if self._draining.is_set():
            return Response(503, {"status": "draining", "class": cls},
                            headers={"Retry-After": "5"})
        fn = getattr(self.backend, "ready", None)
        try:
            ok = bool(fn()) if callable(fn) else True
        except Exception:   # noqa: BLE001 — a broken probe is "not ready"
            log.exception("readiness probe failed")
            ok = False
        if ok:
            return Response(200, {"status": "ready", "class": cls})
        return Response(503, {"status": "warming", "class": cls},
                        headers={"Retry-After": "2"})

    def _backend_failed(self) -> Optional[str]:
        fn = getattr(self.backend, "failed", None)
        return fn() if callable(fn) else None

    def _drain(self, req: Request) -> Response:
        """POST /admin/drain: stop taking new sessions (503 + Retry-After
        on new requests; /readyz flips to draining), finish in-flight
        streams. The backend's own drain hook (engine -> scheduler)
        runs too, so submits that bypass this front shed as well."""
        self._draining.set()
        fn = getattr(self.backend, "drain", None)
        if callable(fn):
            fn()
        log.info("draining: new sessions refused, in-flight streams "
                 "finishing")
        return Response(200, {"status": "draining"})

    def _undrain(self, req: Request) -> Response:
        self._draining.clear()
        fn = getattr(self.backend, "undrain", None)
        if callable(fn):
            fn()
        log.info("undrained: accepting new sessions")
        return Response(200, {"status": "ready"})

    def _shed_if_draining(self, count: bool = True) -> Optional[Response]:
        """Front-level drain shed for every work-accepting endpoint
        (generate/chat AND embed — the embed path never passes through
        scheduler.submit, so the scheduler-level drain alone would leave
        a whole endpoint class accepting new work on a retiring
        replica). Engine backends also shed at the scheduler; backends
        without a drain hook (FakeLLM) are covered here alone.

        ``count=False`` (the embed paths): embeds never move
        serve_requests_total, so moving serve_requests_shed_total for
        them would break the shed <= requests invariant dashboards
        divide by — their drain 503s stay visible via the
        ``serve_draining`` gauge and /readyz instead."""
        if not self._draining.is_set():
            return None
        if count:
            self._m_shed.inc()
        return Response(503, {"error": "server is draining; retry "
                                       "elsewhere"},
                        headers={"Retry-After": "5"})

    def _resolve(self, model: str):
        """Backend for a request's model tag: multi-model backends
        (serve/multi.py) route by tag; single backends serve everything
        (drop-in behavior for whatever name the client sends)."""
        fn = getattr(self.backend, "for_model", None)
        return fn(model) if fn is not None else self.backend

    def _metrics(self, req: Request) -> Response:
        """HTTP-plane registry + the backend's serving-plane gauges (batch
        occupancy, queue depth, KV pool — SURVEY.md §5 metrics plan).
        Multi-model backends emit labeled series
        (``name{model="tag"}``); TYPE lines key on the base name."""
        text = self.metrics.render()
        snap = getattr(self.backend, "metrics_snapshot", None)
        if snap is not None:
            lines = []
            typed: set = set()
            for name, v in sorted(snap().items()):
                base = name.split("{", 1)[0]
                if base not in typed:
                    typed.add(base)
                    kind = ("counter" if base.endswith("_total") else "gauge")
                    lines.append(f"# TYPE {base} {kind}\n")
                lines.append(f"{name} {v}\n")
            text += "".join(lines)
        # Robustness-plane series (process-global): per-site failpoint
        # hit counters (absent entirely when no site ever fired — a
        # production scrape showing ANY failpoint_hits_total series means
        # fault injection is armed) and the shared retry counter from
        # utils/backoff (directory/DHT clients).
        fp = _failpoints.snapshot()
        if fp:
            text += "# TYPE failpoint_hits_total counter\n" + "".join(
                f'failpoint_hits_total{{site="{site}"}} {n}\n'
                for site, n in sorted(fp.items()))
        text += ("# TYPE retry_attempts_total counter\n"
                 f"retry_attempts_total {_backoff.retries_total()}\n")
        # Replica class (serve/disagg.py): a constant 1-gauge labeled
        # with this replica's role — the scrape-side mirror of the
        # /readyz "class" field, so pool membership is also visible to
        # any plain Prometheus scraper.
        text += ("# TYPE serve_replica_class gauge\n"
                 f'serve_replica_class{{class="{self.replica_class}"}} 1\n')
        return Response(200, text, content_type="text/plain; version=0.0.4")

    def _finalize_record(self, model: str, stats: RequestStats,
                         started: float) -> dict:
        total_ns = int((time.monotonic() - started) * 1e9)
        eval_ns = int((stats.total_s or 0) * 1e9)
        ttft_ns = int((stats.ttft_s or 0) * 1e9)
        return {
            "model": model,
            "created_at": now_rfc3339(),
            "done": True,
            "done_reason": "stop",
            "total_duration": total_ns,
            "load_duration": 0,
            "prompt_eval_count": stats.prompt_tokens,
            "prompt_eval_duration": ttft_ns,
            "eval_count": stats.completion_tokens,
            "eval_duration": max(0, eval_ns - ttft_ns),
        }

    def _observe(self, stats: RequestStats) -> None:
        if stats.ttft_s is not None:
            self._m_ttft.observe(stats.ttft_s)
        if stats.total_s is not None:
            self._m_total.observe(stats.total_s)
        self._m_tokens.inc(stats.completion_tokens)

    def _run(self, req_body: dict, prompt: str, key: str,
             wrap, with_context: bool = False,
             headers: Optional[dict] = None,
             entered: Optional[float] = None) -> Response:
        """Shared generate/chat execution. ``key``: response field holding
        text ('response' or 'message'); ``wrap``: delta -> field value;
        ``with_context``: /api/generate's conversation-state round trip
        (request ``context`` ids prepended, final record returns the
        updated ids — Ollama's stateless continuation contract).
        ``headers``: the HTTP request headers — the session id
        (``X-Session-Id`` / ``session`` body field, the router's
        affinity id) rides into the engine for KV tiering. ``entered``:
        when the handler was entered, before it parsed the body (the
        start of the ``api.accept`` span)."""
        # Failpoint: the request-parse/validate site. ``error`` returns
        # a well-formed Ollama error record; ``raise`` rides the
        # router's handler-error envelope (also a well-formed 500).
        act = failpoint("serve.api.parse")
        if act is not None and act.kind == "error":
            self._m_errors.inc()
            return Response(500, {"error": act.msg
                                  or "injected fault: serve.api.parse"})
        model = str(req_body.get("model") or self.backend.name)
        opts = GenerateOptions.from_ollama(req_body.get("options"))
        stream = req_body.get("stream")
        stream = True if stream is None else bool(stream)  # Ollama defaults to streaming
        context: tuple = ()
        if with_context:
            raw_ctx = req_body.get("context") or ()
            # type(t) is int: bools pass isinstance(int); the range bound
            # keeps hostile ids from overflowing int32 device buffers
            # (the backend re-validates against its actual vocab).
            if not (isinstance(raw_ctx, (list, tuple))
                    and all(type(t) is int and 0 <= t < 2 ** 31
                            for t in raw_ctx)):
                return Response(400, {"error": "context must be a list of "
                                               "non-negative token ids"})
            context = tuple(raw_ctx)
        session = str(req_body.get("session") or "")
        if not session and headers is not None:
            session = str(headers.get("x-session-id") or "")
        # grafttrace: adopt the propagated context (router / chat plane /
        # loadgen stamped one) or mint here — this front is then the
        # trace origin and its sample verdict rides the greq fields into
        # the scheduler's spans.
        tctx = _trace.parse_header((headers or {}).get(_trace.HEADER_LC))
        if tctx is None:
            tctx = _trace.mint()
        greq = GenerateRequest(prompt=prompt, model=model, options=opts,
                               context=context, session=session,
                               trace_id=tctx.trace_id,
                               trace_sampled=tctx.sampled)
        backend = self._resolve(model)
        stats = RequestStats()
        self._m_requests.inc()
        self._m_inflight.add(1)
        started = time.monotonic()

        # Drain shed AFTER the request counters move, exactly like the
        # scheduler's OverloadError path below — a drain must not make
        # serve_requests_shed_total climb while serve_requests_total
        # stays flat (shed-ratio dashboards would read >100%).
        shed = self._shed_if_draining()
        if shed is not None:
            self._m_inflight.add(-1)
            return shed

        # Submit happens HERE, before the stream/non-stream split: the
        # scheduler's overload check is eager (fast-fail shedding), so a
        # request shed at capacity gets its 503 + Retry-After in
        # milliseconds — never a queue-deadline burn, and never a
        # mid-NDJSON error record after a 200 status already went out.
        try:
            deltas = backend.generate_stream(greq, stats)
        except OverloadError as e:
            self._m_inflight.add(-1)
            self._m_shed.inc()
            return Response(
                503, {"error": str(e)},
                headers={"Retry-After": str(max(1, round(e.retry_after_s)))})
        except Exception as e:  # noqa: BLE001
            self._m_errors.inc()
            self._m_inflight.add(-1)
            log.exception("submit failed")
            return Response(500, {"error": str(e)})
        if tctx.sampled:
            # This thread's share before the scheduler has the request:
            # the body's JSON, the options, the trace header, submit.
            t_in = started if entered is None else entered
            self.trace.add(tctx.trace_id, "api.accept", t_in,
                           time.monotonic() - t_in, parent="api.request")

        if not stream:
            try:
                text = "".join(deltas)
            except Exception as e:  # noqa: BLE001
                self._m_errors.inc()
                self._m_inflight.add(-1)
                log.exception("generate failed")
                return Response(500, {"error": str(e)})
            self._m_inflight.add(-1)
            self._observe(stats)
            if tctx.sampled:
                self.trace.add(tctx.trace_id, "api.request", started,
                               time.monotonic() - started, endpoint=key,
                               tokens=stats.completion_tokens)
            rec = self._finalize_record(model, stats, started)
            rec[key] = wrap(text)
            if with_context and self._echoes(stats.context):
                rec["context"] = stats.context
            return Response(200, rec)

        def ndjson() -> Iterator[bytes]:
            first = tctx.sampled
            try:
                for delta in deltas:
                    # Failpoint: the per-delta stream-yield site. ``drop``
                    # discards this chunk (truncated-looking text, stream
                    # still terminates cleanly); ``raise`` exercises the
                    # mid-stream error record below.
                    act = failpoint("serve.api.stream")
                    if act is not None and act.kind == "drop":
                        continue
                    chunk = {"model": model, "created_at": now_rfc3339(),
                             key: wrap(delta), "done": False}
                    line = (json.dumps(chunk) + "\n").encode()
                    if first:
                        # From the loop's first push to this thread's
                        # first line: the wake-up, the dequeue, the JSON.
                        first = False
                        if stats.first_push_t is not None:
                            self.trace.add(
                                tctx.trace_id, "api.first_write",
                                stats.first_push_t,
                                time.monotonic() - stats.first_push_t,
                                parent="api.request")
                    yield line
                rec = self._finalize_record(model, stats, started)
                rec[key] = wrap("")
                if with_context and self._echoes(stats.context):
                    rec["context"] = stats.context
                yield (json.dumps(rec) + "\n").encode()
                self._observe(stats)
            except Exception as e:  # noqa: BLE001
                self._m_errors.inc()
                log.exception("stream generate failed")
                yield (json.dumps({"error": str(e), "done": True}) + "\n").encode()
            finally:
                self._m_inflight.add(-1)
                # Span at stream END (error paths included): the
                # envelope covering queue + prefill + the whole decode
                # stream — the router's merge nests the sched.* spans
                # under it.
                if tctx.sampled:
                    self.trace.add(tctx.trace_id, "api.request", started,
                                   time.monotonic() - started,
                                   endpoint=key,
                                   tokens=stats.completion_tokens)

        return Response(200, stream=ndjson(), content_type="application/x-ndjson")

    # -- handlers ------------------------------------------------------------

    def _echoes(self, context) -> bool:
        """Whether a finished exchange's ids go back as ``context``."""
        return context is not None and not (
            0 < self._context_max < len(context))

    def _generate(self, req: Request) -> Response:
        entered = time.monotonic()
        try:
            body = req.json() or {}
        except ValueError:
            return Response(400, {"error": "invalid json"})
        prompt = str(body.get("prompt") or "")
        return self._run(body, prompt, "response", lambda t: t,
                         with_context=True, headers=req.headers,
                         entered=entered)

    def _chat(self, req: Request) -> Response:
        entered = time.monotonic()
        try:
            body = req.json() or {}
        except ValueError:
            return Response(400, {"error": "invalid json"})
        messages = body.get("messages") or []
        if not isinstance(messages, list):
            return Response(400, {"error": "messages must be a list"})
        # The model's own backend renders the chat template (its
        # tokenizer decides llama3 format vs role flattening).
        resolved = self._resolve(str(body.get("model")
                                     or self.backend.name))
        prompt = render_chat_prompt(messages, resolved)
        return self._run(body, prompt, "message",
                         lambda t: {"role": "assistant", "content": t},
                         headers=req.headers, entered=entered)

    def _tags(self, req: Request) -> Response:
        return Response(200, {"models": [
            {"name": m, "model": m, "modified_at": now_rfc3339(),
             "size": 0, "digest": "", "details": {"family": "p2p-llm-chat-tpu"}}
            for m in self.backend.models()
        ]})

    def _show(self, req: Request) -> Response:
        """Ollama `POST /api/show`: model metadata. Clients (CLIs, health
        dashboards) probe this before generating; serve what we know from
        the backend's config when it has one."""
        try:
            body = req.json() or {}
        except ValueError:
            return Response(400, {"error": "invalid json"})
        name = str(body.get("model") or body.get("name") or "")
        models = self.backend.models()
        if (name and name not in models
                and not hasattr(self.backend, "for_model")):
            # Single-model front keeps the strict 404 (pinned contract);
            # multi-model fronts fall back to the default tag here, the
            # SAME drop-in policy /api/generate and /api/chat apply — a
            # client probing /api/show before generating must get the
            # answer the generate would serve.
            return Response(404, {"error": f"model {name!r} not found"})
        cfg = getattr(self._resolve(name or self.backend.name), "config",
                      None)
        details = {"family": "p2p-llm-chat-tpu", "format": "jax",
                   "parameter_size": "", "quantization_level": ""}
        info = {}
        if cfg is not None:
            info = {"general.architecture": "llama" if cfg.num_experts == 0
                    else "mixtral",
                    "llama.context_length": cfg.max_seq_len,
                    "llama.embedding_length": cfg.hidden_size,
                    "llama.block_count": cfg.num_layers,
                    "llama.attention.head_count": cfg.num_heads,
                    # What the model's cache holds a token: per-head K
                    # and V, or one shared latent head (MLA).
                    "llama.attention.head_count_kv": cfg.cache_kv_heads,
                    "llama.vocab_size": cfg.vocab_size}
            if cfg.is_latent:
                info["general.architecture"] = "pangu_ultra_moe"
                info["llama.attention.kv_lora_rank"] = cfg.kv_lora_rank
                info["llama.attention.q_lora_rank"] = cfg.q_lora_rank
                info["llama.rope.dimension_count"] = cfg.qk_rope_head_dim
            if cfg.is_hybrid:
                info["general.architecture"] = "nemotron_h"
                info["nemotron_h.hybrid_pattern"] = cfg.hybrid_pattern
                info["nemotron_h.attention.block_count"] = cfg.cache_layers
                info["nemotron_h.ssm.block_count"] = cfg.ssm_layers
                info["nemotron_h.ssm.state_size"] = cfg.ssm_state_size
            if cfg.ut_steps > 1:
                # A looped stack: ``block_count`` layers of weights walked
                # this many times a token, a cache layer a (pass, layer).
                # Under the family's own key: a mechanism names no model.
                info["llama.loop.pass_count"] = cfg.ut_steps
                info["llama.attention.block_count"] = cfg.cache_layers
        return Response(200, {"modelfile": "", "parameters": "",
                              "template": "", "details": details,
                              "model_info": info})

    def _embed(self, req: Request) -> Response:
        """Ollama `POST /api/embed`: ``input`` is one string or a list;
        responds ``{"embeddings": [[...], ...]}`` plus timing/count fields.
        Backed by models/llama.embed_pooled (mean-pooled final hidden
        states) on the TPU engine, or FakeLLM's hash vectors."""
        try:
            body = req.json() or {}
        except ValueError:
            return Response(400, {"error": "invalid json"})
        shed = self._shed_if_draining(count=False)
        if shed is not None:
            return shed
        model = str(body.get("model") or self.backend.name)
        fn = getattr(self._resolve(model), "embed", None)
        if fn is None:
            # Ollama's own wording for non-embedding models.
            return Response(400, {"error": "this model does not support embeddings"})
        inp = body.get("input")
        if inp is None:
            inp = body.get("prompt")        # tolerated, like Ollama
        if inp is not None and not isinstance(inp, (str, list)):
            return Response(400, {"error": "input must be a string or list of strings"})
        texts = [inp] if isinstance(inp, str) else list(inp or [])
        if not all(isinstance(t, str) for t in texts):
            return Response(400, {"error": "input must be a string or list of strings"})
        started = time.monotonic()
        try:
            vecs, n_tokens = fn(texts)
        except Exception as e:  # noqa: BLE001
            self._m_errors.inc()
            log.exception("embed failed")
            return Response(500, {"error": str(e)})
        return Response(200, {
            "model": model,
            "embeddings": vecs,
            "total_duration": int((time.monotonic() - started) * 1e9),
            "load_duration": 0,
            "prompt_eval_count": n_tokens,
        })

    def _embeddings_legacy(self, req: Request) -> Response:
        """Ollama's legacy `POST /api/embeddings` ({"prompt": ...} ->
        {"embedding": [...]}) — kept because older clients still call it."""
        try:
            body = req.json() or {}
        except ValueError:
            return Response(400, {"error": "invalid json"})
        shed = self._shed_if_draining(count=False)
        if shed is not None:
            return shed
        fn = getattr(self._resolve(str(body.get("model")
                                       or self.backend.name)),
                     "embed", None)
        if fn is None:
            return Response(400, {"error": "this model does not support embeddings"})
        prompt = body.get("prompt")
        if not isinstance(prompt, str):
            return Response(400, {"error": "prompt must be a string"})
        try:
            vecs, _ = fn([prompt])
        except Exception as e:  # noqa: BLE001
            self._m_errors.inc()
            log.exception("embed failed")
            return Response(500, {"error": str(e)})
        return Response(200, {"embedding": vecs[0]})

    def _prefix_list(self, req: Request) -> Response:
        """GET /admin/prefix: {token_hash: {len, hits}} for this
        replica's cached prefixes. 501 when the backend has no prefix
        store (FakeLLM, prefix cache disabled) so the router skips it."""
        fn = getattr(self.backend, "prefix_hashes", None)
        if fn is None:
            return Response(501, {"error": "no prefix store"})
        got = fn()
        if got is None:
            return Response(501, {"error": "no prefix store"})
        return Response(200, {"prefixes": got})

    def _prefix_export(self, req: Request) -> Response:
        """GET /admin/prefix/export?h=<token_hash>: the serialized entry
        (ids + KV, serve/prefix.py wire format) for a peer replica."""
        fn = getattr(self.backend, "prefix_export", None)
        if fn is None:
            return Response(501, {"error": "no prefix store"})
        h = str(req.query.get("h") or "")
        if not h:
            return Response(400, {"error": "missing h=<token_hash>"})
        data = fn(h)
        if data is None:
            return Response(404, {"error": f"prefix {h} not cached"})
        return Response(200, data, content_type="application/octet-stream")

    def _prefix_import(self, req: Request) -> Response:
        """POST /admin/prefix/import: install a peer's prefix entry.
        Body is either the raw exported payload, or JSON
        {"from": <peer base url>, "h": <token_hash>} — the PULL form the
        router uses, so KV bytes flow replica-to-replica and the router
        never buffers them."""
        fn = getattr(self.backend, "prefix_import", None)
        if fn is None:
            return Response(501, {"error": "no prefix store"})
        data = req.body or b""
        if data[:1] == b"{":
            try:
                spec = req.json() or {}
            except ValueError:
                return Response(400, {"error": "invalid json"})
            src = str(spec.get("from") or "")
            h = str(spec.get("h") or "")
            if not src or not h:
                return Response(400, {"error": "need from + h"})
            import urllib.request
            # The replica-to-replica pull is a proxy hop: the router's
            # trace/session context rides it so the export fetch shows
            # up on the same timeline as the import that caused it.
            hdrs = {}
            raw_tid = req.headers.get(_trace.HEADER_LC)
            if raw_tid:
                hdrs[_trace.HEADER] = raw_tid
            sid = req.headers.get("x-session-id")
            if sid:
                hdrs["X-Session-Id"] = sid
            try:
                with urllib.request.urlopen(urllib.request.Request(
                        f"{src.rstrip('/')}/admin/prefix/export?h={h}",
                        headers=hdrs),
                        timeout=30.0) as r:
                    data = r.read()
            except Exception as e:   # noqa: BLE001 — peer may be gone
                return Response(502, {"error": f"pull from {src} "
                                               f"failed: {e}"})
        entry = fn(data)
        if entry is None:
            return Response(400, {"error": "malformed or incompatible "
                                           "prefix payload"})
        return Response(200, {"status": "ok", "len": entry.length,
                              "hash": entry.token_hash})

    # -- live session migration (/admin/session, serve/kv_tier.py) -----------

    def _session_backend(self):
        """The backend's session-tier surface, or None when this replica
        has none (FakeLLM, tiering disabled, multi-model front) — every
        /admin/session endpoint then answers 501 so the router skips the
        replica instead of retrying it."""
        fn = getattr(self.backend, "session_list", None)
        if fn is None or fn() is None:
            return None
        return self.backend

    def _session_list(self, req: Request) -> Response:
        """GET /admin/session: {key: {len, nbytes, parked, idle_s}} —
        the migration control surface (small JSON, no KV bytes)."""
        be = self._session_backend()
        if be is None:
            return Response(501, {"error": "no session tier"})
        return Response(200, {"sessions": be.session_list() or {}})

    def _session_export(self, req: Request) -> Response:
        """GET /admin/session/export?key=<session key>: the serialized
        parked payload (a resident session parks first via the
        scheduler's park-all handshake). The session is RETAINED —
        removal happens only on the destination's ack (forget)."""
        be = self._session_backend()
        if be is None:
            return Response(501, {"error": "no session tier"})
        key = str(req.query.get("key") or "")
        if not key:
            return Response(400, {"error": "missing key=<session key>"})
        data = be.session_export(key)
        if data is None:
            return Response(404, {"error": f"session {key!r} not open"})
        return Response(200, data, content_type="application/octet-stream")

    def _session_import(self, req: Request) -> Response:
        """POST /admin/session/import: install a peer's exported
        session. Body is the raw payload, or the PULL form
        {"from": <peer base url>, "key": <session key>} the router
        sends — KV bytes flow replica-to-replica directly."""
        be = self._session_backend()
        if be is None:
            return Response(501, {"error": "no session tier"})
        tctx = _trace.parse_header(req.headers.get(_trace.HEADER_LC))
        t_imp = time.monotonic()
        data = req.body or b""
        if data[:1] == b"{":
            try:
                spec = req.json() or {}
            except ValueError:
                return Response(400, {"error": "invalid json"})
            src = str(spec.get("from") or "")
            key = str(spec.get("key") or "")
            if not src or not key:
                return Response(400, {"error": "need from + key"})
            import urllib.parse
            import urllib.request
            # The pull is a proxy hop: forward the caller's trace
            # header so the source replica's export span lands on the
            # same timeline, and the migrating session's identity as
            # X-Session-Id for the source's access logs.
            hdrs = {"X-Session-Id": key}
            raw_tid = req.headers.get(_trace.HEADER_LC)
            if raw_tid:
                hdrs[_trace.HEADER] = raw_tid
            try:
                q = urllib.parse.urlencode({"key": key})
                with urllib.request.urlopen(urllib.request.Request(
                        f"{src.rstrip('/')}/admin/session/export?{q}",
                        headers=hdrs),
                        timeout=30.0) as r:
                    data = r.read()
            except Exception as e:   # noqa: BLE001 — peer may be gone
                return Response(502, {"error": f"pull from {src} "
                                               f"failed: {e}"})
        sess = be.session_import(data)
        if sess is None:
            return Response(400, {"error": "malformed or incompatible "
                                           "session payload"})
        # disagg.import: the decode replica's KV pull during a handoff
        # (covers the replica-to-replica export fetch when the PULL
        # form was used). Traced only when the router forwarded the
        # original request's header.
        if tctx is not None and tctx.sampled:
            self.trace.add(tctx.trace_id, "disagg.import", t_imp,
                           time.monotonic() - t_imp,
                           key=sess.key, tokens=sess.length)
        return Response(200, {"status": "ok", "key": sess.key,
                              "len": sess.length})

    def _session_forget(self, req: Request) -> Response:
        """POST /admin/session/forget {"key": k}: the migration ack —
        drop the (parked) source copy now that the destination owns the
        session. Not an eviction: capacity dashboards must not read
        migrations as pressure."""
        be = self._session_backend()
        if be is None:
            return Response(501, {"error": "no session tier"})
        try:
            body = req.json() or {}
        except ValueError:
            return Response(400, {"error": "invalid json"})
        key = str(body.get("key") or "")
        if not key:
            return Response(400, {"error": "missing key"})
        if not be.session_forget(key):
            return Response(404, {"error": f"session {key!r} not parked "
                                           "here"})
        return Response(200, {"status": "forgotten", "key": key})

    def _session_park_all(self, req: Request) -> Response:
        """POST /admin/session/park_all: demote every resident session
        to its host-RAM (exportable) form — the drain-as-migration
        pre-step."""
        be = self._session_backend()
        if be is None:
            return Response(501, {"error": "no session tier"})
        be.session_park_all()
        return Response(200, {"status": "parked",
                              "sessions": be.session_list() or {}})

    # -- disaggregated prefill (serve/disagg.py round 14) --------------------

    def _disagg_prefill(self, req: Request) -> Response:
        """POST /admin/disagg/prefill {"path", "body"}: run the wrapped
        generate/chat request's chunked prefill to completion and
        retain its KV as an exportable session (the prefill side of the
        prefill→decode handoff). The prompt is rendered EXACTLY as the
        real endpoint would render it — same chat template, same
        context rules — so the decode replica's normalization of the
        original request matches the parked token ids. Answers:

        - 200 ``{"key", "len", "parked"}`` — parked, ready to pull;
        - 422 — this request cannot ride the handoff (too short to
          index, no session retained): route it un-disaggregated;
        - 501 — this backend has no prefill-park surface (FakeLLM,
          tiering off): the router stops asking;
        - 503 — draining/saturated, the ordinary shed contract."""
        # Fast 501 for backends that can never park (FakeLLM): the
        # router memoizes it and stops asking. Multi-model fronts pass
        # through — their per-model ENGINES carry the surface, checked
        # after resolution below.
        if (getattr(self.backend, "prefill_park", None) is None
                and getattr(self.backend, "for_model", None) is None):
            return Response(501, {"error": "no disagg prefill surface"})
        shed = self._shed_if_draining(count=False)
        if shed is not None:
            return shed
        try:
            outer = req.json() or {}
        except ValueError:
            return Response(400, {"error": "invalid json"})
        if not isinstance(outer, dict):
            return Response(400, {"error": "request body must be an "
                                           "object"})
        path = str(outer.get("path") or "/api/generate")
        body = outer.get("body")
        if not isinstance(body, dict):
            return Response(400, {"error": "need a body object"})
        model = str(body.get("model") or self.backend.name)
        backend = self._resolve(model)
        context: tuple = ()
        if path == "/api/chat":
            messages = body.get("messages") or []
            if not isinstance(messages, list):
                return Response(400, {"error": "messages must be a list"})
            prompt = render_chat_prompt(messages, backend)
        else:
            prompt = str(body.get("prompt") or "")
            raw_ctx = body.get("context") or ()
            if not (isinstance(raw_ctx, (list, tuple))
                    and all(type(t) is int and 0 <= t < 2 ** 31
                            for t in raw_ctx)):
                return Response(400, {"error": "context must be a list "
                                               "of non-negative token "
                                               "ids"})
            context = tuple(raw_ctx)
        session = str(body.get("session") or "")
        if not session:
            session = str(req.headers.get("x-session-id") or "")
        # The router forwards the original request's trace header on
        # the handoff's step-1 call, so the prefill replica's chunked
        # prefill lands under the SAME trace id the decode replica's
        # wake span carries — the merged timeline shows the handoff
        # end-to-end. No header => untraced (never mint here: this is
        # an internal hop, not an ingress).
        tctx = _trace.parse_header(req.headers.get(_trace.HEADER_LC))
        greq = GenerateRequest(
            prompt=prompt, model=model,
            options=GenerateOptions.from_ollama(body.get("options")),
            context=context, session=session,
            trace_id=tctx.trace_id if tctx else "",
            trace_sampled=bool(tctx and tctx.sampled))
        t_park = time.monotonic()
        fn = getattr(backend, "prefill_park", None)
        sl = getattr(backend, "session_list", None)
        if fn is None or sl is None or sl() is None:
            # No surface or no KV tier on the resolved engine: a
            # PERMANENT answer — 501 lets the router memoize instead of
            # re-asking per conversation (422 below is per-request).
            return Response(501, {"error": "no disagg prefill surface"})
        try:
            meta = fn(greq)
        except OverloadError as e:
            return Response(
                503, {"error": str(e)},
                headers={"Retry-After": str(max(1,
                                                round(e.retry_after_s)))})
        except Exception as e:  # noqa: BLE001 — a failed prefill is a 500
            self._m_errors.inc()
            log.exception("disagg prefill failed")
            return Response(500, {"error": str(e)})
        if meta is None:
            return Response(422, {"error": "request cannot ride the "
                                           "handoff (unindexable or "
                                           "prefill not retained)"})
        if tctx is not None and tctx.sampled:
            self.trace.add(tctx.trace_id, "disagg.prefill_park", t_park,
                           time.monotonic() - t_park,
                           key=str(meta.get("key") or ""),
                           tokens=int(meta.get("len") or 0))
        return Response(200, {"status": "parked", **meta})

    # -- grafttrace (obs/, round 15) -----------------------------------------

    def _trace_list(self, req: Request) -> Response:
        """GET /admin/trace: trace ids held by this replica's bounded
        store plus store stats; ``?id=<trace id>`` returns that trace's
        recorded spans (wall-anchored ``t0_ms`` — directly mergeable
        with other replicas' spans for the same id). The router's own
        /admin/trace builds the cross-replica timeline from these."""
        tid = str(req.query.get("id") or "")
        if tid:
            spans = self.trace.get(tid)
            if not spans:
                return Response(404, {"error": f"trace {tid!r} not held "
                                               "(evicted or never "
                                               "sampled here)"})
            return Response(200, {"id": tid, "spans": spans})
        # Stats nest under their own key: the store's stats() also
        # counts "traces" and would clobber the id list if splatted.
        return Response(200, {"traces": self.trace.ids(),
                              "stats": self.trace.stats()})

    def _trace_dump(self, req: Request) -> Response:
        """POST /admin/trace/dump: write the scheduler flight-recorder
        ring to its durable JSON file on demand (same artifact the
        watchdog writes on a stall) and return the path. 501 when the
        backend has no flight surface (FakeLLM)."""
        fn = getattr(self.backend, "flight_dump", None)
        if fn is None:
            return Response(501, {"error": "no flight recorder (backend "
                                           "has no scheduler loop)"})
        try:
            path = fn("on_demand")
        except OSError as e:
            return Response(500, {"error": f"flight dump failed: {e}"})
        return Response(200, {"status": "dumped", "path": path})

    def _unsupported(self, req: Request) -> Response:
        return Response(501, {
            "error": "model management is not supported: models are "
                     "provisioned from checkpoints at startup (CKPT_DIR; "
                     "see README serve section)"})

    def _ps(self, req: Request) -> Response:
        """Ollama `GET /api/ps`: loaded models. Everything we serve is
        resident (no lazy loading), so list the backend's models."""
        return Response(200, {"models": [
            {"name": m, "model": m, "size": 0, "digest": "",
             "expires_at": "", "size_vram": 0}
            for m in self.backend.models()
        ]})

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "OllamaServer":
        self._server = HttpServer(self.router, self.addr_cfg).start()
        # Tag this replica's spans with the bound address so the
        # router's merged timeline names which replica each span ran on.
        self.trace.replica = self._server.addr
        log.info("serve API (%s backend) on %s", self.backend.name, self._server.addr)
        return self

    @property
    def url(self) -> str:
        assert self._server is not None
        return self._server.url

    def serve_forever(self) -> None:
        """Serve until killed — or until the backend reports a terminal
        failure (a failed warmup), which ends the PROCESS non-zero: a
        launcher waiting on /readyz sees a dead child at once instead of
        polling a server that can never go ready."""
        self.start()
        while True:
            time.sleep(1.0)
            err = self._backend_failed()
            if err:
                log.error("backend failed (%s); exiting", err)
                self.stop()
                raise SystemExit(1)

    def stop(self) -> None:
        if self._server:
            self._server.stop()


def main() -> None:
    """Entry point: ``SERVE_BACKEND=fake`` (default) serves FakeLLM,
    ``tpu`` the real engine (serve/engine.py — on the TPU, or boot
    fails; ``JAX_PLATFORMS=cpu`` pins the CPU on purpose).

    Multi-host mode switch (docs/serving.md Round-10): setting
    ``SERVE_ROUTER_UPSTREAMS`` starts the replica router
    (serve/router.py — N independent full-stack engines, this process
    only routes); setting ``SERVE_COORDINATOR`` starts the lockstep
    SPMD plane (serve/multihost.py — one model instance spanning
    hosts). They are alternatives; configuring both is a boot error
    rather than a silent pick."""
    ups = env_or("SERVE_ROUTER_UPSTREAMS", "")
    if ups:
        if env_or("SERVE_COORDINATOR", ""):
            raise SystemExit(
                "SERVE_ROUTER_UPSTREAMS and SERVE_COORDINATOR are "
                "mutually exclusive modes (replica-router vs lockstep "
                "SPMD); set exactly one")
        from .router import build_router_from_env
        build_router_from_env().serve_forever()
        return
    from .backend import FakeLLM
    backend_kind = env_or("SERVE_BACKEND", "fake")
    if backend_kind == "fake":
        backend: Backend = FakeLLM()
    else:
        try:
            from .engine import build_engine_from_env
        except ImportError as e:
            raise SystemExit(f"SERVE_BACKEND={backend_kind} needs serve.engine: {e}")
        backend = build_engine_from_env()
    if getattr(backend, "is_follower", False):
        # Multi-host follower: no HTTP front — mirror the leader's
        # programs until it broadcasts shutdown (serve/multihost.py).
        backend.follower_loop()
        return
    OllamaServer(backend).serve_forever()


if __name__ == "__main__":
    main()

"""Multi-model serving: several resident backends behind one Ollama front.

Ollama hosts many models and routes each request by its ``model`` tag;
this is the in-tree equivalent for the serve front (serve/api.py). Each
tag maps to its own fully-independent backend (for TPU engines: own
scheduler, own KV pool, own decode loop — requests for different models
never share a batch), and the HTTP front resolves the backend per
request via :meth:`for_model`.

Routing policy, chosen for drop-in compatibility over strictness: an
unknown tag serves the DEFAULT model instead of 404ing. The reference UI
sends whatever ``LLM_MODEL`` names (llama3.1 by default,
web/streamlit_app.py:28) — a server whose resident model is tagged
differently must still answer it, exactly like the single-model front
always has.

Configured via ``SERVE_MODELS`` (serve/engine.py):
``tag=config,tag2=config2`` — e.g. ``SERVE_MODELS=tiny=tiny,moe=tiny-moe``.
"""

from __future__ import annotations

from typing import Iterator, Optional

from .backend import Backend, GenerateRequest, RequestStats


class MultiBackend:
    """Route requests across named backends; the Backend protocol plus a
    ``for_model`` resolver the API front uses for chat templates, embeds
    and /api/show."""

    def __init__(self, backends: dict[str, Backend],
                 default: Optional[str] = None) -> None:
        if not backends:
            raise ValueError("need at least one backend")
        self.backends = dict(backends)
        self.default = default if default is not None else next(iter(backends))
        if self.default not in self.backends:
            raise ValueError(f"default {self.default!r} not among "
                             f"{sorted(self.backends)}")
        self.name = self.default

    def for_model(self, model: str) -> Backend:
        """Exact tag match, else the default (drop-in fallback)."""
        return self.backends.get(model, self.backends[self.default])

    def generate_stream(self, req: GenerateRequest,
                        stats: Optional[RequestStats] = None) -> Iterator[str]:
        return self.for_model(req.model).generate_stream(req, stats)

    def models(self) -> list[str]:
        return list(self.backends)

    def metrics_snapshot(self) -> dict[str, float]:
        """Per-model gauges with Prometheus labels (the /metrics renderer
        groups TYPE lines by base name)."""
        out: dict[str, float] = {}
        for tag, b in self.backends.items():
            snap = getattr(b, "metrics_snapshot", None)
            if snap is None:
                continue
            # Prometheus label-value escaping (backslash, quote, newline
            # — the exposition format's required set): an unescaped tag
            # would break the whole /metrics page for scrapers.
            esc = (tag.replace("\\", "\\\\").replace('"', '\\"')
                   .replace("\n", "\\n"))
            for k, v in snap().items():
                if k.endswith("}"):
                    # Already-labeled series (the per-draft-source spec
                    # keys): merge the model label into the existing
                    # brace block — a second {model=...} suffix would be
                    # malformed exposition and break the whole scrape.
                    out[f'{k[:-1]},model="{esc}"}}'] = v
                else:
                    out[f'{k}{{model="{esc}"}}'] = v
        return out

    def ready(self) -> bool:
        """/readyz gating: the front is ready only when EVERY engine is
        (requests route by tag — a half-warmed fleet would serve some
        tags with cold-compile TTFTs). Backends without a probe count
        as ready."""
        for b in self.backends.values():
            fn = getattr(b, "ready", None)
            if callable(fn) and not fn():
                return False
        return True

    def failed(self) -> Optional[str]:
        """First engine's terminal failure (engine.TPUEngine.failed), or
        None: the front serves every tag or exits."""
        for tag, b in self.backends.items():
            fn = getattr(b, "failed", None)
            err = fn() if callable(fn) else None
            if err:
                return f"{tag}: {err}"
        return None

    def warmup(self, *args, **kwargs) -> None:
        for b in self.backends.values():
            fn = getattr(b, "warmup", None)
            if fn is not None:
                fn(*args, **kwargs)

    def drain(self) -> None:
        """Replica drain (serve/router.py): draining the front drains
        EVERY engine — the replica retires as a unit, not per tag."""
        for b in self.backends.values():
            fn = getattr(b, "drain", None)
            if fn is not None:
                fn()

    def undrain(self) -> None:
        for b in self.backends.values():
            fn = getattr(b, "undrain", None)
            if fn is not None:
                fn()

    def stop(self) -> None:
        for b in self.backends.values():
            fn = getattr(b, "stop", None)
            if fn is not None:
                fn()

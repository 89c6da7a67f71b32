"""p2p_llm_chat_tpu — a TPU-native P2P chat framework with an in-tree LLM co-pilot.

A from-scratch build with the capabilities of NajyFannoun/P2P-LLM-Chat-Go
(see SURVEY.md): per-user P2P chat nodes with encrypted peer
streams and a local HTTP API, a username->peer directory service, an
optional circuit relay, a chat web UI with an AI reply co-pilot — plus,
replacing the reference's external Ollama dependency, a native JAX/XLA
TPU serving stack (llama-family + Mixtral MoE models, Pallas paged-KV
attention, continuous batching, tensor/expert parallelism over ICI).

Subpackages
-----------
- ``proto``     — chat wire schema (reference: go/cmd/node/proto/message.go)
- ``inbox``     — per-node message buffer (reference: go/cmd/node/main.go:97-128)
- ``p2p``       — encrypted P2P transport substrate (reference L0: go-libp2p)
- ``directory`` — username->peer registry service + client (go/cmd/directory)
- ``node``      — per-user chat node daemon (go/cmd/node/main.go)
- ``relay``     — circuit relay daemon (go/cmd/relay/main.go)
- ``serve``     — TPU LLM serving: Ollama-compatible HTTP front, continuous
                  batching scheduler, inference engine (replaces reference L4)
- ``models``    — JAX model definitions (llama family, Mixtral MoE)
- ``ops``       — Pallas TPU kernels (paged attention, flash attention)
- ``parallel``  — device mesh / sharding rules / collectives (DP, PP, EP,
                  SP/ring, TP; multi-host DCN entry)
- ``utils``     — config, logging, metrics, tiny HTTP framework, n-gram
                  drafting, native-library loader
"""

__version__ = "0.1.0"

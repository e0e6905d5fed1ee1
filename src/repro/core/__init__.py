"""Core of the paper's contribution: Skip-LoRA topology + Skip-Cache.

- ``compute_model``: Table-1 compute-type taxonomy with closed-form FLOPs.
- ``methods``: the eight fine-tuning methods of Sections 3-4 at MLP scale.
- ``skip_cache``: the forward-activation cache (Section 4.2), device-sharded.
- ``finetune``: Algorithm 1 (populate epoch + cached epochs).
- ``lm_adapters``: Skip-LoRA adapters for transformer LMs (framework scale).
- ``cache_engine``: tiered HBM/host cache placement (DESIGN.md §4).
- ``adapter_pool``: slot-based multi-tenant adapter registry for serving
  (DESIGN.md §7); feeds the grouped Pallas kernel.
- ``batch_plan``: the one epoch batch planner (wrap/mask tail semantics)
  behind every trainer's index matrices.
- ``runtime``: the session runtime — serve + ingest + fleet adapt
  interleaved over one pool/engine/compiled-fn cache (DESIGN.md §9),
  mesh-native since §10: sessions shard tenants over an explicit device
  mesh and restart from event-boundary checkpoints.
"""

"""Ragged inference engine configuration.

Reference: inference/v2/config_v2.py (RaggedInferenceEngineConfig with
DSStateManagerConfig: max_tracked_sequences, max_ragged_batch_size,
max_ragged_sequence_count, memory_config) — plus the TPU-native knobs: KV
block size and prefill bucket granularity (static-shape compilation caches).
"""

from dataclasses import dataclass, field
from typing import Optional


@dataclass
class DSStateManagerConfig:
    max_tracked_sequences: int = 64          # concurrent sequences
    max_ragged_batch_size: int = 768         # tokens per put() (prefill cap)
    max_ragged_sequence_count: int = 512
    max_seq_len: int = 2048
    num_blocks: int = 256                    # KV pool size (incl. null block)
    block_size: int = 64                     # tokens per KV block
    memory_reserve_fraction: float = 0.0     # reference memory_config analogue
    # share full KV blocks across requests with identical token prefixes
    # (registered at flush, matched at the next arrival, LRU-evicted
    # under pool pressure) — beyond the reference; see ragged_manager.py
    enable_prefix_caching: bool = False
    # cold-block KV spill tier (ragged/spill.py): prefix-cache eviction
    # demotes block CONTENT to host RAM (and optionally disk) keyed by
    # the prefix digest; a later arrival with a spilled prefix restores
    # it between scheduler steps instead of recomputing — idle
    # conversations stop costing HBM. Requires enable_prefix_caching
    # (spilled blocks are identified by their chain digests).
    enable_kv_spill: bool = False
    kv_spill_host_bytes: int = 64 << 20      # host-tier LRU budget
    kv_spill_dir: Optional[str] = None       # optional disk tier
    kv_spill_disk_bytes: int = 256 << 20     # disk-tier LRU budget
    # disk-tier namespace under kv_spill_dir: every tier writes its
    # entries into its OWN subdirectory, so replicas sharing a scratch
    # directory never clobber each other. None (default) derives a
    # unique per-instance namespace; an explicit name must be unique
    # per directory (a claimed collision raises typed at engine
    # construction) and is what a fleet orchestrator pins so the
    # router's session resurrection can name the namespace to adopt.
    kv_spill_namespace: Optional[str] = None

    def __post_init__(self):
        if self.enable_kv_spill and not self.enable_prefix_caching:
            raise ValueError(
                "enable_kv_spill requires enable_prefix_caching: spilled "
                "blocks are keyed by the prefix chain digests the index "
                "computes")
        if self.kv_spill_namespace is not None:
            ns = self.kv_spill_namespace
            if not ns or "/" in ns or "\\" in ns or ns in (".", ".."):
                raise ValueError(
                    f"kv_spill_namespace must be a single path "
                    f"component (got {ns!r})")
        if self.enable_kv_spill:
            # spill budgets are registered tunables: bad values fail
            # naming the registry entry and its documented range
            from ...runtime import tunables
            for key in ("kv_spill_host_bytes", "kv_spill_disk_bytes"):
                name = f"state_manager.{key}"
                tunables.check(name, getattr(self, key), label=key)
                tunables.observe(name, getattr(self, key), "config")


@dataclass
class RaggedInferenceEngineConfig:
    state_manager: DSStateManagerConfig = field(
        default_factory=DSStateManagerConfig)
    tensor_parallel_size: int = 1
    # expert parallelism for MoE serving: experts shard over an "expert"
    # mesh axis (reference v2 ships per-arch sharding helpers,
    # model_implementations/*/; here it is one mesh axis away)
    expert_parallel_size: int = 1
    dtype: str = "bfloat16"
    # no prompt pads to it (put() is the ragged step, whose buckets are
    # powers of two): the scheduler's default prompt chunk, the bucket of
    # the one-sequence passes (the n-gram verify, the draft's catch-up)
    # and the prompt chunk of memory_report's representative step
    prefill_bucket: int = 64
    use_paged_kernel: bool = True            # Pallas decode attention kernel
    # weight-only quantization (0 = off): weights rest in HBM as int8 /
    # packed int4 + per-block scales, dequantized inside the jitted
    # forward where XLA fuses into the consuming matmul (same machinery
    # as the v1 engine, inference/quantization.py) — halves/quarters
    # weight HBM, freeing KV-pool headroom
    quant_bits: int = 0
    # int8 KV-cache pool (~0.5x bf16 bytes -> ~2x tokens, i.e. ~2x
    # concurrent sequences at a fixed pool budget): writes quantize
    # against a running per-(block, kv-head) absmax, reads dequantize.
    # Serves through the SAME Pallas decode/ragged kernels as bf16 — they
    # stream the int8 pages, read the row's scales from SMEM and
    # dequantize in VMEM — so fused decode windows, the ragged unified
    # program and the SplitFuse fast path all keep their compiled shape.
    kv_quant: bool = False
    # the type the recurrent state of a model with linear-attention
    # layers is KEPT in between launches (the update itself runs in
    # float32): "float32", or "bfloat16" at half the bytes, which rounds
    # the state at every token and drifts from the recurrence (the
    # benchmark's control for such a cell)
    state_dtype: str = "float32"
    # fused multi-token decode: up to K decode steps run in ONE jitted
    # device loop (cache write, paged attention, sampling, EOS masking,
    # arithmetic block-table advance over pre-allocated blocks) with a
    # single [N, K] int32 transfer per window instead of a Python
    # round-trip per token. K is fixed per compiled program (batch rows
    # still pad to the power-of-two buckets), so the compile cache stays
    # bounded; per-row budgets mask shorter tails. 1 = the per-token
    # fallback path.
    decode_window: int = 8
    # multi-tenant batched LoRA serving (0 = off): hot adapter slots in
    # the stacked device bank. Slot 0 is reserved for the base model
    # (all-zero delta — bit-exact no-op), so the bank holds
    # max_lora_adapters live fine-tunes at slots 1..max. Per-row adapter
    # indices ride the per-token descriptor layout and the deltas are
    # gathered inside the jitted step (paged_model._lora_delta); the
    # bank is allocated at engine init so hot-deploying an adapter is a
    # same-shape slot update — no recompile.
    max_lora_adapters: int = 0
    lora_rank: int = 8                       # rank of every bank slot
    # speculative decoding source per request: "auto" routes between the
    # host n-gram index and the in-window draft model via the
    # hysteresis-armed accept-rate chooser (engine_v2.SpecChooser);
    # "ngram" / "draft" pin the source
    spec_mode: str = "auto"
    seed: int = 0

    def __post_init__(self):
        # serving geometry knobs are registered tunables
        # (runtime/tunables.py): validate against the documented range
        # and publish the effective value + provenance for /statusz
        from ...runtime import tunables
        for key, name in (("decode_window", "serving.decode_window"),
                          ("prefill_bucket", "serving.prefill_bucket")):
            tunables.check(name, getattr(self, key), label=key)
            tunables.observe(name, getattr(self, key), "config")
        if self.spec_mode not in ("auto", "ngram", "draft"):
            raise ValueError(
                f"spec_mode must be 'auto', 'ngram' or 'draft', got "
                f"{self.spec_mode!r}")
        if self.state_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"state_dtype must be 'float32' or 'bfloat16', got "
                f"{self.state_dtype!r}")
        if self.max_lora_adapters < 0:
            raise ValueError("max_lora_adapters must be >= 0")
        if self.max_lora_adapters and self.lora_rank < 1:
            raise ValueError("lora_rank must be >= 1 when the adapter "
                             "bank is enabled")

    @classmethod
    def from_dict(cls, d: dict) -> "RaggedInferenceEngineConfig":
        d = dict(d or {})
        if "ragged_attention" in d:
            raise ValueError(
                "ragged_attention is gone: every put() runs the ragged "
                "step, and the stitched prefill / continue / decode "
                "dispatch it selected no longer exists; drop the key "
                f"(got {d['ragged_attention']!r})")
        sm = d.pop("state_manager", {})
        if isinstance(sm, dict):
            sm = DSStateManagerConfig(**sm)
        return cls(state_manager=sm, **d)

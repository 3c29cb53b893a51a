"""Mixer registry: one table from mixer kind to its init/forward/decode hooks.

Every sequence-mixing block family (full attention, sliding-window
attention, MLA, Mamba-2 SSD, RG-LRU) registers a :class:`MixerSpec` here.
The model stack (:mod:`repro.models.model`) and the serving runtime
(:mod:`repro.serve`) dispatch through this table instead of per-call-site
``if mixer == ...`` chains, so adding a mixer kind is one registration —
the H2 lesson (arXiv 2505.17548): heterogeneity is absorbed by the
framework, not by every caller.

Each spec also declares *how its decode state lives under paged serving*
(the HyperOffload per-state-kind policy, arXiv 2602.00748):

  - ``PAGED``     per-layer KV pages indexed through block tables
                  (full attention, MLA latents);
  - ``SLOT``      O(1) per-request dense state seated in a fixed decode
                  slot (SSD recurrent state, RG-LRU state, conv tails);
  - ``WINDOWED``  paged, but at most ``ceil(window/block) + 1`` blocks
                  are ever live per request — out-of-window blocks are
                  freed back to the ``BlockManager`` (sliding-window
                  attention).

``model_state_layout(cfg)`` resolves a whole config against the registry
and is the single serving-support oracle: an unregistered mixer kind is a
typed ``ServePlanError`` naming the offending mixer and rule, raised
before anything jits.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, LOCAL_ATTN, MLA, RGLRU, SSD
from repro.models import attention, mamba2 as m2, mla as mla_mod, \
    rglru as rg_mod

# decode-state kinds under paged serving ------------------------------------
PAGED = "paged"
SLOT = "slot"
WINDOWED = "windowed"

STATE_KINDS = (PAGED, SLOT, WINDOWED)


@dataclasses.dataclass(frozen=True)
class MixerSpec:
    """Everything the stack and the serving runtime need for one mixer kind.

    Dense hooks (``init``/``forward``/``decode``/``init_cache``) receive the
    whole sublayer param dict and index their own ``param_key`` entry.
    Serving hooks (``init_state``/``decode_paged``/``prefill_paged``) define
    the mixer's :data:`state` layout under the paged pool.
    """
    kind: str                  # configs.base mixer constant
    state: str                 # PAGED | SLOT | WINDOWED
    param_key: str             # sublayer dict entry the params live under
    init: Callable             # (cfg, key) -> param subtree
    forward: Callable          # (p, h, positions, cfg, *, window, want_cache)
    decode: Callable           # (p, h, pos, cfg, cache, *, window) -> (y, c)
    init_cache: Callable       # (cfg, batch, eff_len, dtype) -> cache pytree
    init_state: Callable       # (cfg, *, num_blocks, block_size, num_slots,
    #                             dtype) -> one-layer serving-state leaves
    decode_paged: Callable     # (p, h, positions, cfg, state, tables, *,
    #                             [layer,] block_size, window, slot_mask,
    #                             kernels) -> (y, state)
    prefill_paged: Callable    # (p, h, starts, limits, slots, cfg, state,
    #                             tables, *, [layer,] block_size, window,
    #                             kernels) -> (y, state)
    #   batched: h (P, C, D); starts/limits/slots (P,) traced vectors;
    #   tables (P, W) — all scheduled prompt chunks in ONE call, filler
    #   rows padded to limit 0 / the null slot
    # which serving hooks have a fused block-table-walking Pallas lowering
    # under kernels="fused" (subset of ("decode", "prefill")); hooks not
    # listed silently take their composed path — slot mixers have no
    # table walk to fuse, MLA prefill needs in-kernel decompression
    # (deferred)
    fused_hooks: Tuple[str, ...] = ()
    # True: the serving hooks take EVERY layer's stacked leaves (L, ...)
    # plus a traced ``layer`` index and update that layer in place (paged
    # attention and MLA latents).  False: they take one layer's leaves,
    # which decode_layer / prefill_layer slice out and write back (SSD
    # and RG-LRU slot state).
    in_place: bool = False

    def window(self, cfg) -> Optional[int]:
        """Static sliding window this mixer serves under (None = unbounded)."""
        return cfg.sliding_window if self.state == WINDOWED else None

    def decode_layer(self, p, h, positions, cfg, state, layer, tables, **kw):
        """``decode_paged`` on layer ``layer`` of the stacked ``state``."""
        if self.in_place:
            return self.decode_paged(p, h, positions, cfg, state, tables,
                                     layer=layer, **kw)
        y, new = self.decode_paged(p, h, positions, cfg,
                                   _layer_of(state, layer), tables, **kw)
        return y, _put_layer(state, new, layer)

    def prefill_layer(self, p, h, starts, limits, slots, cfg, state, layer,
                      tables, **kw):
        """``prefill_paged`` on layer ``layer`` of the stacked ``state``."""
        if self.in_place:
            return self.prefill_paged(p, h, starts, limits, slots, cfg,
                                      state, tables, layer=layer, **kw)
        y, new = self.prefill_paged(p, h, starts, limits, slots, cfg,
                                    _layer_of(state, layer), tables, **kw)
        return y, _put_layer(state, new, layer)


def _layer_of(state, layer):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
        state)


def _put_layer(state, new, layer):
    return jax.tree.map(
        lambda a, n: jax.lax.dynamic_update_index_in_dim(
            a, n.astype(a.dtype), layer, 0), state, new)


_REGISTRY: dict = {}


def register_mixer(spec: MixerSpec) -> MixerSpec:
    assert spec.state in STATE_KINDS, spec.state
    _REGISTRY[spec.kind] = spec
    return spec


def get_mixer(kind: str) -> MixerSpec:
    try:
        return _REGISTRY[kind]
    except KeyError:
        raise ValueError(
            f"unknown mixer kind {kind!r}: no MixerSpec registered "
            f"(registered: {sorted(_REGISTRY)}). Register one in "
            "repro.models.mixers and list the kind in "
            "configs.base.MIXER_KINDS.") from None


def registered_kinds() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_window(cfg, kind: str, window_override: Optional[int]):
    """Dense-path window: WINDOWED mixers pin their registry window (the
    same one the paged serving path uses, so dense/served parity holds by
    construction); other mixers accept the caller's override (long_500k
    windowed-decode mode)."""
    spec = get_mixer(kind)
    if spec.state == WINDOWED:
        return spec.window(cfg)
    return window_override


# ---------------------------------------------------------------------------
# stack segmentation (shared by model.py and the serving state layout)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Segment:
    kinds: Tuple[Tuple[str, str], ...]   # (mixer, ffn) per sub-layer
    repeat: int


def segments(cfg) -> Tuple[Segment, ...]:
    kinds = cfg.block_kinds()
    if cfg.family == "hybrid":
        pat = len(cfg.rglru.block_pattern)
        n_macro, tail = cfg.num_layers // pat, cfg.num_layers % pat
        segs = [Segment(tuple(kinds[:pat]), n_macro)]
        if tail:
            segs.append(Segment(tuple(kinds[n_macro * pat:]), 1))
        return tuple(segs)
    # otherwise: group maximal runs of identical (mixer, ffn)
    segs = []
    run_kind, run_len = kinds[0], 0
    for kd in kinds:
        if kd == run_kind:
            run_len += 1
        else:
            segs.append(Segment((run_kind,), run_len))
            run_kind, run_len = kd, 1
    segs.append(Segment((run_kind,), run_len))
    return tuple(segs)


# ---------------------------------------------------------------------------
# serving state layout: the whole-model resolution of the registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SegmentStates:
    name: str                             # "seg0", "seg1", ...
    repeat: int
    kinds: Tuple[Tuple[str, str], ...]    # (mixer, ffn) per sub-layer
    specs: Tuple[MixerSpec, ...]          # one per sub-layer


@dataclasses.dataclass(frozen=True)
class ModelStateLayout:
    """How one model's decode state lives under the paged serving pool."""
    segments: Tuple[SegmentStates, ...]
    has_slot_state: bool                  # any SLOT mixer in the stack
    has_paged_state: bool                 # any PAGED/WINDOWED mixer
    has_windowed_state: bool              # any WINDOWED mixer
    free_window: Optional[int]            # out-of-window block freeing is
    #   sound only when EVERY paged mixer is windowed; then this is the
    #   largest window any layer still needs (None otherwise)

    @property
    def pure_paged(self) -> bool:
        """Only full (unwindowed) paged state: CoW prefix forks and the
        dense-prefill disagg handoff are sound.  A WINDOWED mixer
        disqualifies even when mixed with full attention (its dense
        prefill cache is a ring of ``window`` positions, not the
        absolute-position pages the handoff seats)."""
        return not self.has_slot_state and not self.has_windowed_state


def check_disagg_supported(cfg, layout: "ModelStateLayout") -> None:
    """Disaggregated prefill hands the dense prefill cache over as pages —
    sound only for pure (unwindowed) paged layouts.  One rule, enforced
    identically by the serving runtime and by ``explain()`` preflight."""
    if layout.pure_paged:
        return
    from repro.api.errors import ServePlanError
    offending = sorted({(sp.kind, sp.state) for seg in layout.segments
                        for sp in seg.specs if sp.state != PAGED})
    raise ServePlanError(
        "prefill/decode disaggregation needs pure paged decode state "
        "(rule: the dense prefill cache is handed over as pages); "
        f"{cfg.name} has "
        + ", ".join(f"mixer {k!r} with state rule {s!r}"
                    for k, s in offending)
        + " — serve it aggregated (chunked prefill on one mesh).")


def model_state_layout(cfg) -> ModelStateLayout:
    """Resolve ``cfg`` against the mixer registry; typed error if unservable."""
    segs = []
    windows: list = []
    has_slot = has_paged = has_windowed = False
    all_paged_windowed = True
    for si, seg in enumerate(segments(cfg)):
        specs = []
        for mixer, _ in seg.kinds:
            try:
                spec = get_mixer(mixer)
            except ValueError as e:
                from repro.api.errors import ServePlanError
                raise ServePlanError(
                    f"{cfg.name} is not servable: segment {si} uses mixer "
                    f"{mixer!r}, which has no registered MixerSpec (rule: "
                    "every mixer kind must register init/decode/prefill "
                    "hooks plus a paged/slot/windowed StateSpec in "
                    "repro.models.mixers).") from e
            specs.append(spec)
            if spec.state == SLOT:
                has_slot = True
            else:
                has_paged = True
                if spec.state == WINDOWED:
                    has_windowed = True
                    windows.append(spec.window(cfg))
                else:
                    all_paged_windowed = False
        segs.append(SegmentStates(f"seg{si}", seg.repeat, seg.kinds,
                                  tuple(specs)))
    free_window = (max(windows) if has_paged and all_paged_windowed and windows
                   else None)
    return ModelStateLayout(tuple(segs), has_slot, has_paged, has_windowed,
                            free_window)


# ---------------------------------------------------------------------------
# registrations
# ---------------------------------------------------------------------------
def _attn_forward(p, h, positions, cfg, *, window, want_cache):
    if want_cache:
        return attention.attn_prefill(p["attn"], h, positions, cfg,
                                      window=window)
    return attention.attn_forward(p["attn"], h, positions, cfg,
                                  window=window), None


def _attn_init_state(cfg, *, num_blocks, block_size, num_slots, dtype):
    # lane-dense pages: a page row holds every KV head side by side, so
    # (KV=2, hd=64) fills the 128 lanes of a TPU tile instead of half
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (num_blocks, block_size, kv * hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _gate_slot_update(result, old_state, slot_mask):
    """Keep inactive decode seats' recurrent state untouched.

    The batched decode step advances EVERY seat (empty/prefilling seats
    run a dummy token).  Paged mixers are naturally safe — dummy writes
    land in the null block — but a slot mixer's recurrence would absorb
    the dummy, so the update is gated per seat: ``slot_mask`` (B,) bool,
    True where the seat holds a RUNNING request.
    """
    y, new_state = result
    if slot_mask is None:
        return y, new_state

    def sel(new, old):
        m = slot_mask.reshape((slot_mask.shape[0],) + (1,) * (new.ndim - 1))
        return jnp.where(m, new.astype(old.dtype), old)

    return y, jax.tree.map(sel, new_state, old_state)


def _attn_decode_paged(p, h, positions, cfg, state, tables, *, layer,
                       block_size, window, slot_mask=None,
                       kernels="composed"):
    return attention.attn_decode_paged(p["attn"], h, positions, cfg, state,
                                       tables, layer=layer,
                                       block_size=block_size, window=window,
                                       kernels=kernels)


def _attn_prefill_paged(p, h, starts, limits, slots, cfg, state, tables, *,
                        layer, block_size, window, kernels="composed"):
    return attention.attn_prefill_paged(p["attn"], h, starts, limits, cfg,
                                        state, tables, layer=layer,
                                        block_size=block_size, window=window,
                                        kernels=kernels)


for _kind, _state in ((ATTN, PAGED), (LOCAL_ATTN, WINDOWED)):
    register_mixer(MixerSpec(
        kind=_kind, state=_state, param_key="attn",
        init=lambda cfg, key: attention.init_attention(cfg, key),
        forward=_attn_forward,
        decode=lambda p, h, pos, cfg, cache, *, window:
            attention.attn_decode(p["attn"], h, pos, cfg, cache,
                                  window=window),
        init_cache=lambda cfg, batch, eff_len, dtype:
            attention.init_kv_cache(cfg, batch, eff_len, dtype),
        init_state=_attn_init_state,
        decode_paged=_attn_decode_paged,
        prefill_paged=_attn_prefill_paged,
        fused_hooks=("decode", "prefill"),
        in_place=True,
    ))


def _mla_forward(p, h, positions, cfg, *, window, want_cache):
    if want_cache:
        return mla_mod.mla_forward(p["attn"], h, positions, cfg,
                                   window=window, return_cache=True)
    return mla_mod.mla_forward(p["attn"], h, positions, cfg,
                               window=window), None


register_mixer(MixerSpec(
    kind=MLA, state=PAGED, param_key="attn",
    init=lambda cfg, key: mla_mod.init_mla(cfg, key),
    forward=_mla_forward,
    decode=lambda p, h, pos, cfg, cache, *, window:
        mla_mod.mla_decode(p["attn"], h, pos, cfg, cache, window=window),
    init_cache=lambda cfg, batch, eff_len, dtype:
        mla_mod.init_mla_cache(cfg, batch, eff_len, dtype),
    init_state=lambda cfg, *, num_blocks, block_size, num_slots, dtype:
        mla_mod.init_mla_pool(cfg, num_blocks, block_size, dtype),
    decode_paged=lambda p, h, positions, cfg, state, tables, *, layer,
        block_size, window, slot_mask=None, kernels="composed":
        mla_mod.mla_decode_paged(
            p["attn"], h, positions, cfg, state, tables, layer=layer,
            block_size=block_size, kernels=kernels),
    prefill_paged=lambda p, h, starts, limits, slots, cfg, state, tables, *,
        layer, block_size, window, kernels="composed":
        mla_mod.mla_prefill_chunk_paged(
            p["attn"], h, starts, limits, cfg, state, tables, layer=layer,
            block_size=block_size, kernels=kernels),
    fused_hooks=("decode",),
    in_place=True,
))


def _ssd_forward(p, h, positions, cfg, *, window, want_cache):
    if want_cache:
        return m2.mamba2_forward(p["mixer"], h, cfg, return_cache=True)
    return m2.mamba2_forward(p["mixer"], h, cfg), None


register_mixer(MixerSpec(
    kind=SSD, state=SLOT, param_key="mixer",
    init=lambda cfg, key: m2.init_mamba2(cfg, key),
    forward=_ssd_forward,
    decode=lambda p, h, pos, cfg, cache, *, window:
        m2.mamba2_decode(p["mixer"], h, cfg, cache),
    init_cache=lambda cfg, batch, eff_len, dtype:
        m2.init_mamba2_cache(cfg, batch, dtype),
    init_state=lambda cfg, *, num_blocks, block_size, num_slots, dtype:
        m2.init_mamba2_cache(cfg, num_slots, dtype),
    decode_paged=lambda p, h, positions, cfg, state, tables, *, block_size,
        window, slot_mask=None, kernels="composed": _gate_slot_update(
            m2.mamba2_decode(p["mixer"], h, cfg, state), state, slot_mask),
    prefill_paged=lambda p, h, starts, limits, slots, cfg, state, tables, *,
        block_size, window, kernels="composed": m2.mamba2_prefill_chunk(
            p["mixer"], h, starts, limits, slots, cfg, state),
))


def _rglru_forward(p, h, positions, cfg, *, window, want_cache):
    if want_cache:
        return rg_mod.rglru_forward(p["mixer"], h, cfg, return_cache=True)
    return rg_mod.rglru_forward(p["mixer"], h, cfg), None


register_mixer(MixerSpec(
    kind=RGLRU, state=SLOT, param_key="mixer",
    init=lambda cfg, key: rg_mod.init_rglru(cfg, key),
    forward=_rglru_forward,
    decode=lambda p, h, pos, cfg, cache, *, window:
        rg_mod.rglru_decode(p["mixer"], h, cfg, cache),
    init_cache=lambda cfg, batch, eff_len, dtype:
        rg_mod.init_rglru_cache(cfg, batch, dtype),
    init_state=lambda cfg, *, num_blocks, block_size, num_slots, dtype:
        rg_mod.init_rglru_cache(cfg, num_slots, dtype),
    decode_paged=lambda p, h, positions, cfg, state, tables, *, block_size,
        window, slot_mask=None, kernels="composed": _gate_slot_update(
            rg_mod.rglru_decode(p["mixer"], h, cfg, state), state, slot_mask),
    prefill_paged=lambda p, h, starts, limits, slots, cfg, state, tables, *,
        block_size, window, kernels="composed": rg_mod.rglru_prefill_chunk(
            p["mixer"], h, starts, limits, slots, cfg, state),
))

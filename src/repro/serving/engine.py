"""Serving engine: continuous batching over fixed decode slots, with every
byte routed through the MRM memory control plane.

The engine is an orchestrator over two subsystems that talk through an
explicit :class:`StepPlan` / :class:`StepReport` interface:

- :class:`ComputeBackend` — the JAX compute path, the paged compute plane
  (DESIGN.md §10): one pool of compute pages per cache family, owned by
  the backend and indexed through page tables, on which the jitted first-
  chunk, extend and batched decode step programs compute in place.
- :class:`MemoryPlane`  — the MRM control plane: weights live in a region
  of the chosen tier (written once at deploy, read wholesale every model
  pass — §2.2); KV pages go through :class:`PagedKVManager` (DCM retention
  = expected session lifetime, capacity pressure resolved by an explicit
  eviction/spill/recompute policy); refresh/migrate/drop deadlines are
  serviced as simulation time advances.

Every memory-plane page owns one compute page for its life, so prefix
reuse is *real* in both planes (DESIGN.md §6): at admission the prompt is
matched against the radix prefix tree; on a hit the matched page-aligned
tokens are attached in the memory plane (no KV writes) and their compute
pages are spliced into the request's page table (no copy), and prefill
continues via extend from the boundary. A hit therefore cuts prefill
chunks, metered KV writes, and step latency together. **Every** prompt
runs *unpadded* on the one chunked path (DESIGN.md §5): token ``i`` sits
at position ``prefix_len + i`` for every request whatever the flags, so
shared prefixes are position-aligned across prompt lengths (multi-turn
chat, shared system prompts, RAG fan-out all match) — "whole-prompt"
prefill is simply the maximal first chunk of the same path. A match may
also end mid-page: with ``tail_copy`` the sub-page tail's rows are copied
into the borrower's own page (metered read + write, DESIGN.md §9) and
extend resumes from the exact token boundary.

Compute reuse covers every mixer family (DESIGN.md §8): attention and MLA
stacks resume on the shared KV pages at any boundary; SSM and hybrid
stacks resume on the state page at the last page boundary the match
covers, which holds the recurrent state there.

Chunked prefill: prompts longer than ``chunk_tokens`` (or than the
clamp in ``ServeEngine._chunk_plan``) are fed to the model in pieces
interleaved with decode rounds, bounding inter-token latency for
resident sessions.

Step time (simulation) is modelled per tier from the bytes each phase
actually moved and each tier's read/write bandwidth (tiers progress in
parallel; the slowest tier bounds the step) — so tokens/s and tokens/J
reflect the memory system under test, which is exactly the paper's figure
of merit.
"""
from __future__ import annotations

import contextlib
import sys
import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.simulator import MemorySystem
from repro.kernels.paged_attention import ops as attn_kernel_ops
from repro.models import transformer as tfm
from repro.models.attention import paged_pages_gathered
from repro.serving.kv_cache import PagedKVManager
from repro.serving.radix import PrefixMatch
from repro.serving.scheduler import ContinuousBatchScheduler, Request
from repro.serving.telemetry import mark, recorder, span

_NO_BUILD = contextlib.nullcontext()

# sys.getrefcount of an object that one list alone holds, read while a
# generator walks that list: any more means other code holds it too
_SOLE_REFS = max(sys.getrefcount(a) for a in [object()])


@dataclass
class EngineConfig:
    max_slots: int = 4
    max_cache_len: int = 256
    max_prefills_per_step: int = 2
    weight_tier: str = "mrm"
    kv_tier: str = "mrm"
    page_tokens: int = 64
    expected_session_s: float = 600.0
    eos_token: int = 1
    greedy: bool = True
    # radix prefix reuse [53]: match page-aligned prompt prefixes, share
    # their KV pages, and skip their prefill compute (prompts always run
    # unpadded so prefixes stay position-aligned across lengths)
    prefix_caching: bool = True
    # sub-page tail reuse (DESIGN.md §9): a match ending mid-page copies
    # the shared tail into the borrower's page and extend resumes from
    # the exact token boundary (positional stacks)
    tail_copy: bool = True
    # chunked prefill: feed prompts in `chunk_tokens` pieces interleaved
    # with decode rounds (None = one maximal chunk per prompt, clamped to
    # the smallest per-layer ring — the same code path)
    chunk_tokens: Optional[int] = None
    # capacity-pressure policy for the KV tier (see PagedKVManager):
    # "evict-lru" | "spill" | "recompute" | "none" (legacy silent drops)
    kv_pressure_policy: str = "evict-lru"
    kv_spill_tier: Optional[str] = None
    kv_high_watermark: Optional[float] = 0.92
    # reuse -> retention programming (paper §4): a radix node reused
    # `radix_hot_threshold` times is promoted to `radix_hot_retention_s`
    # DCM retention, placed in `radix_hot_tier` when set ("auto" lets
    # core.tiering.solve_placement pick it); unlocked leaves idle past
    # `radix_cold_ttl_s` decay (spill when a spill tier exists, else drop)
    radix_hot_threshold: int = 4
    radix_hot_retention_s: float = 3600.0
    radix_hot_tier: Optional[str] = None
    radix_cold_ttl_s: Optional[float] = None
    # pressure-driven demotion (DESIGN.md §9): a hot node is re-programmed
    # back to short retention (metered) before leaf eviction may reach it
    demote_on_pressure: bool = False
    # regression guard (the PR 4 clobbering class): verify after every
    # decode round that no other session's compute page was written
    audit_decode_masking: bool = False
    # the paged compute plane (DESIGN.md §10) is the only compute path;
    # the benchmark's configs still name this key, and False is refused
    paged_kernel: bool = True
    # reliability plane (DESIGN.md §11): inject age-driven bit flips into
    # the paged KV/state pages of decoding sessions, anchored so a page
    # exactly at its programmed retention sees this RBER (None = off).
    # Whether flips are corrected follows the MemorySystem's ecc_profile:
    # under an active profile, critical flips land only on uncorrectable
    # blocks and near-deadline pages scrub-on-read instead (metered).
    inject_rber: Optional[float] = None
    inject_seed: int = 0
    # abandonment (DESIGN.md §12): queued requests older than this are
    # dropped before admission — the user hung up before first token.
    # Sessions already holding slots always run to completion (None = off).
    abandon_after_s: Optional[float] = None


# ---------------------------------------------------------------------------
# StepPlan / StepReport: the contract between scheduler, compute and memory
# ---------------------------------------------------------------------------


@dataclass
class PrefillChunk:
    """One piece of a prompt scheduled for this step."""
    slot: int
    request_id: int
    tokens: np.ndarray
    offset: int    # absolute start position (incl. meta/frontend prefix)
    first: bool    # creates the slot's caches (runs full prefill)
    last: bool     # completes the prompt (samples the first output token)


@dataclass
class StepPlan:
    """What this engine step will do: the scheduler builds it, the
    ComputeBackend executes it, the MemoryPlane meters it."""
    prefill: List[PrefillChunk] = field(default_factory=list)
    decode: List[int] = field(default_factory=list)  # slots


@dataclass
class StepReport:
    """What an engine step did, with the per-tier byte/latency breakdown."""
    step_s: float = 0.0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    finished: int = 0
    bytes_by_tier: Dict[str, dict] = field(default_factory=dict)

    @property
    def bytes(self) -> float:
        return sum(t["read_bytes"] + t["write_bytes"]
                   for t in self.bytes_by_tier.values())


@dataclass
class _SlotPrefill:
    """Continuation state of a (possibly radix-shortened) chunked prefill:
    how far into the prompt the slot's pages already reach — a prefix hit
    starts `done` at the reused boundary (which, with sub-page tail reuse,
    need not be page-aligned) instead of 0."""
    req: Request
    tokens: np.ndarray            # prompt tokens (always unpadded)
    chunk: int
    key: Optional[np.ndarray]     # radix key: prefix_len sentinels + tokens
    match: Optional[PrefixMatch]
    done: int = 0   # tokens of `tokens` already in the slot's pages
    grid: Optional[int] = None    # point stacks: page-aligned chunking

    def next_chunk(self, slot: int, prefix_len: int) -> PrefillChunk:
        end = min(self.done + self.chunk, len(self.tokens))
        if self.grid:
            # point stacks chunk on the position-space page grid:
            # recurrent-state arithmetic depends on the chunk partition, so
            # every engine must cut prompts identically (resumption from a
            # state page stays bit-equal to a cold run) (DESIGN.md §8)
            nxt = ((prefix_len + self.done) // self.grid + 1) * self.grid \
                - prefix_len
            end = min(end, max(nxt, self.done + 1))
        return PrefillChunk(slot, self.req.request_id,
                            self.tokens[self.done:end],
                            offset=prefix_len + self.done,
                            first=self.done == 0,
                            last=end == len(self.tokens))


# ---------------------------------------------------------------------------
# ComputeBackend: the JAX half
# ---------------------------------------------------------------------------


class ComputeBackend:
    """Real-model compute over fixed decode slots on the paged compute
    plane (DESIGN.md §10): owns the compute-page pool, one pooled page
    array per cache family, and the per-slot positions/tokens; runs the
    jitted first-chunk prefill (prompts are never padded — programs are
    keyed by the exact chunk length), chunked-prefill continuation
    (extend) and batched decode in place on the pages each launch's page
    table lists. Knows nothing about tiers or retention."""

    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig):
        self.cfg = cfg
        self.ecfg = ecfg
        self.params = params
        B = ecfg.max_slots
        self.positions = np.full((B,), -1, np.int64)  # last written position
        self.last_tokens = np.zeros((B, 1) if cfg.n_codebooks == 1
                                    else (B, 1, cfg.n_codebooks), np.int32)
        # paged step programs built (traced + compiled or loaded) per kind,
        # and the (kind, length, width) keys built on the current pool
        self.builds = {k: {"count": 0, "seconds": 0.0}
                       for k in ("first", "extend", "decode")}
        self._built: set = set()
        self.pool_grows = 0
        self.pool_copies = 0
        # per paged program kind: KV pages the served attention gathers,
        # the pages its sequences' keys occupy, and the ragged-kernel
        # calls (one per kernel layer per launch) (DESIGN.md §3)
        self.kv_pages_gathered = {k: 0 for k in self.builds}
        self.kv_pages_attended = {k: 0 for k in self.builds}
        self.attention_kernel_launches = {k: 0 for k in self.builds}
        self._attn_specs = [s for s in cfg.layer_specs()
                            if s.kind in ("attn", "mla", "hybrid")]
        # Page 0 is the reserved null page (gathered for padded table
        # slots; auto-masked by slot-derived positions). The pool lives on
        # the weights' device, so replicas placed on different devices
        # compute there
        self.page_tokens = ecfg.page_tokens
        n0 = max(16, 1 + B * -(-ecfg.max_cache_len // ecfg.page_tokens))
        device = next(iter(jax.tree.leaves(params)[0].devices()))
        self.attn_kernel = self.kernel_serves(cfg, device.platform)
        self.paged_caches = jax.device_put(tfm.init_paged_caches(
            cfg, n0, ecfg.page_tokens, jnp.dtype(cfg.dtype)), device)
        self._free = list(range(n0 - 1, 0, -1))  # pop() -> lowest id
        self._paged_first_jit: Dict[tuple, callable] = {}
        self._paged_extend_jit: Dict[tuple, callable] = {}
        self._paged_decode_jit: Dict[int, callable] = {}

    # -- paged compute-page pool (DESIGN.md §10) -----------------------
    @property
    def paged_caches(self):
        """The compute-page pool: per group, per unit, a tree of page
        arrays stacked over the group's layers. Each read builds a fresh
        tree around the backend's own leaves, so code that keeps it is
        seen holding the leaves at the next launch (``_launch``)."""
        return jax.tree.unflatten(self._pool_def, self._pool)

    @paged_caches.setter
    def paged_caches(self, tree) -> None:
        self._pool, self._pool_def = jax.tree.flatten(tree)

    @staticmethod
    def table_width(n_pages: int) -> int:
        """Power-of-2 page-table width bucket (bounds jit retraces)."""
        return max(1, 1 << (max(1, n_pages) - 1).bit_length())

    def _grow_pool(self) -> None:
        """Double the page pool — zeros appended on the page axis of every
        cache-family leaf. jit'd steps retrace on the new pool shape, so
        every key builds again."""
        self.pool_grows += 1
        self._built.clear()
        grown = []

        def widen(a):
            pad = jnp.zeros(a.shape[:1] + (a.shape[1],) + a.shape[2:],
                            a.dtype)
            grown.append(a.shape[1])
            return jnp.concatenate([a, pad], axis=1)

        self.paged_caches = jax.tree.map(widen, self.paged_caches)
        old = grown[0]
        self._free.extend(range(2 * old - 1, old - 1, -1))

    def alloc_page(self) -> int:
        if not self._free:
            self._grow_pool()
        return self._free.pop()

    def free_page(self, pid: int) -> None:
        """Return a compute page to the pool. No zeroing needed: a reused
        page's stale rows sit above the new owner's written length, where
        slot-derived key positions exceed every query position (masked)."""
        self._free.append(pid)

    def copy_page_rows(self, src: int, dst: int, n: int) -> None:
        """Copy rows [0, n) of compute page `src` into `dst` across every
        cache family — the sub-page tail seeding primitive (DESIGN.md §9):
        the only bytes a prefix hit ever copies on the paged plane."""
        with span("backend.copy_rows", rows=n):
            self.paged_caches = jax.tree.map(
                lambda a: a.at[:, dst, :n].set(a[:, src, :n]),
                self.paged_caches)

    def pool_pages(self) -> int:
        """Pages in the compute-page pool."""
        return int(jax.tree.leaves(self.paged_caches)[0].shape[1])

    def _build_scope(self, kind: str, length: int, width: int):
        """Around a paged step program's call: a ``backend.build`` span
        and the ``builds`` count the first time its key runs on this
        pool (the call traces and compiles, or loads, the program)."""
        if (kind, length, width) in self._built:
            return _NO_BUILD
        return self._building(kind, length, width)

    @contextlib.contextmanager
    def _building(self, kind: str, length: int, width: int):
        t0 = time.perf_counter()
        with span("backend.build", kind=kind, length=length, width=width):
            yield
        self._built.add((kind, length, width))
        b = self.builds[kind]
        b["count"] += 1
        b["seconds"] += time.perf_counter() - t0

    def export_pages(self, ids: List[int]):
        """Host-side copy of the listed compute pages (page axis first in
        each leaf slice) — the migration wire format."""
        idx = np.asarray(ids, np.int32)
        return jax.tree.map(lambda a: np.asarray(a[:, idx]),
                            self.paged_caches)

    def import_pages(self, ids: List[int], data) -> None:
        idx = jnp.asarray(np.asarray(ids, np.int32))
        self.paged_caches = jax.tree.map(
            lambda a, d: a.at[:, idx].set(jnp.asarray(d, a.dtype)),
            self.paged_caches, data)

    def pages_compatible(self, data) -> bool:
        """Foreign page data is adoptable only when its tree structure and
        per-page leaf shapes/dtypes match this pool exactly."""
        try:
            if (jax.tree.structure(data)
                    != jax.tree.structure(self.paged_caches)):
                return False
        except Exception:
            return False
        return all(
            d.shape[0] == a.shape[0] and d.shape[2:] == a.shape[2:]
            and d.dtype == a.dtype
            for d, a in zip(jax.tree.leaves(data),
                            jax.tree.leaves(self.paged_caches)))

    @staticmethod
    def kernel_serves(cfg: ModelConfig, platform: str) -> bool:
        """Whether the paged steps attend KV pages through the ragged
        kernel (DESIGN.md §10): where the kernel says it serves this
        page geometry on this platform (``ops.serves``: a TPU, and a
        geometry its compiler takes); else the rows form. Latent (MLA)
        pages always take the rows form."""
        return any(s.kind in ("attn", "hybrid")
                   for s in cfg.layer_specs()) and attn_kernel_ops.serves(
            2 * cfg.n_kv_heads, cfg.resolved_head_dim, platform)

    @staticmethod
    def paged_program(cfg: ModelConfig, kind: str, page_tokens: int,
                      attn_kernel: bool = False):
        """The jitted paged step program of ``kind`` ("first", "extend" or
        "decode") as the backend launches it: ``(params, pool, ...) ->
        (logits, pool)``. The pool argument is donated, so the program
        owns it and updates it in place (DESIGN.md §10). ``attn_kernel``
        (``kernel_serves``) attends through the ragged kernel."""
        kw = dict(page_tokens=page_tokens, attn_kernel=attn_kernel)
        if kind == "first":
            fn = lambda p, c, batch, tbl: tfm.paged_prefill(
                cfg, p, batch, c, tbl, **kw)
        elif kind == "extend":
            fn = lambda p, c, t, off, tbl: tfm.paged_extend(
                cfg, p, c, t, off, tbl, **kw)
        else:
            fn = lambda p, c, t, pos, tbl, act: tfm.paged_decode(
                cfg, p, c, t, pos, tbl, active=act, **kw)
        return jax.jit(fn, donate_argnums=1)

    def _paged_first_fn(self, length: int, W: int):
        key = (length, W)
        if key not in self._paged_first_jit:
            self._paged_first_jit[key] = self.paged_program(
                self.cfg, "first", self.page_tokens, self.attn_kernel)
        return self._paged_first_jit[key]

    def _paged_extend_fn(self, length: int, W: int):
        key = (length, W)
        if key not in self._paged_extend_jit:
            self._paged_extend_jit[key] = self.paged_program(
                self.cfg, "extend", self.page_tokens, self.attn_kernel)
        return self._paged_extend_jit[key]

    def _paged_decode_fn(self, W: int):
        if W not in self._paged_decode_jit:
            self._paged_decode_jit[W] = self.paged_program(
                self.cfg, "decode", self.page_tokens, self.attn_kernel)
        return self._paged_decode_jit[W]

    def _launch_table(self, tables) -> np.ndarray:
        """Page tables as the step programs take them. On the kernel path
        they are padded with null slots to a multiple of
        ``ops.TABLE_QUANTUM``, so that launches of every narrower width
        share one program: the kernel reads no slot past a sequence's
        keys. The rows form reads every slot, so there a table keeps its
        power-of-2 width."""
        tables = np.asarray(tables, np.int32)
        if not self.attn_kernel or any(s.kind == "mla"
                                       for s in self._attn_specs):
            return tables
        pad = -tables.shape[-1] % attn_kernel_ops.TABLE_QUANTUM
        return np.pad(tables, [(0, 0)] * (tables.ndim - 1) + [(0, pad)])

    def _count_kv_pages(self, sp, kind: str, tables: np.ndarray,
                        q_len: int, keys: np.ndarray) -> None:
        """Count, on the host, the KV pages a paged launch's attention
        gathers against the pages its sequences' keys occupy, each read
        once, over the attention layers; the launch's span ``sp`` carries
        both, and the path that attended. ``tables`` is the launch's
        (R, W) page table, ``q_len`` the query rows of each of its R
        sequences, ``keys`` (R,) each sequence's keys after the launch (0
        for an idle decode row). The rows form reads R·q_len·W pages per
        layer (``paged_pages_gathered``); the kernel reads the grid's page
        DMAs (``ops.launch_pages``), on every attention layer but latent
        (MLA) ones, which keep the rows form."""
        pt, (R, W), specs = self.page_tokens, tables.shape, self._attn_specs
        # kernel-attended layers by window
        kernel = Counter(s.window for s in specs
                         if self.attn_kernel and s.kind != "mla")
        kernel_layers = sum(kernel.values())
        gathered = paged_pages_gathered(R * q_len, W) * (len(specs)
                                                         - kernel_layers)
        if kernel_layers:
            cu = np.arange(R + 1) * q_len
            gathered += sum(n * attn_kernel_ops.launch_pages(
                tables, cu, keys, page_size=pt,
                head_dim=self.cfg.resolved_head_dim, max_q_len=q_len,
                window=w) for w, n in kernel.items())
            self.attention_kernel_launches[kind] += kernel_layers
        attended = int(np.sum(-(-np.asarray(keys) // pt))) * len(specs)
        self.kv_pages_gathered[kind] += gathered
        self.kv_pages_attended[kind] += attended
        sp.attrs.update(kv_pages_gathered=gathered,
                        kv_pages_attended=attended,
                        path="kernel" if kernel_layers else "rows")

    def _launch(self, program, *args):
        """Run a paged step program on the pool and rebind the pool to its
        result; returns the logits. The program consumes the pool it is
        handed. Where other code still holds a pool leaf, the leaves are
        copied first, so that holder's arrays stay live. ``pool_copies``
        counts the launches that leave the input pool unconsumed: copied
        here, or not donated by the platform."""
        leaves = self._pool
        if any(sys.getrefcount(a) > _SOLE_REFS for a in leaves):
            leaves = [jnp.copy(a) for a in leaves]
        first = self._pool[0]
        logits, self.paged_caches = program(
            self.params, jax.tree.unflatten(self._pool_def, leaves), *args)
        if not first.is_deleted():
            self.pool_copies += 1
        return logits

    def prefix_len(self) -> int:
        return self.cfg.n_meta_tokens + (self.cfg.n_frontend_tokens
                                         if self.cfg.frontend == "vision" else 0)

    def sample(self, logits):
        # the head is padded past the vocabulary (vocab_pad_to); padding
        # ids are not tokens and must never be sampled
        logits = logits[..., :self.cfg.vocab_size]
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # -- StepPlan execution --------------------------------------------
    def run_prefill_chunk(self, ck: PrefillChunk,
                          page_table: np.ndarray) -> Optional[np.ndarray]:
        """Execute one prefill chunk in place on the pool pages listed in
        ``page_table`` (the request's session pages). Returns the sampled
        next token when the chunk completes the prompt, else None."""
        toks = np.asarray(ck.tokens, np.int32)
        W = int(np.shape(page_table)[-1])
        with span("backend.prefill", width=W, rows=toks.shape[0]) as sp:
            kind = "first" if ck.first else "extend"
            with span("backend.inputs"):
                # a first chunk's query rows include the meta/frontend
                # prefix; the chunk's keys end where its tokens do
                keys = ck.offset + toks.shape[0]
                self._count_kv_pages(
                    sp, kind, np.asarray(page_table)[None],
                    keys if ck.first else toks.shape[0], np.array([keys]))
                tbl = jnp.asarray(self._launch_table(page_table),
                                  jnp.int32)[None]
                W = tbl.shape[-1]
                tok_d = jnp.asarray(toks)[None]
                if ck.first:
                    batch = {"tokens": tok_d}
                    if self.cfg.frontend == "vision":
                        batch["image_embeds"] = jnp.zeros(
                            (1, self.cfg.n_frontend_tokens,
                             self.cfg.d_model), jnp.dtype(self.cfg.dtype))
            with span("backend.launch"), \
                    self._build_scope(kind, toks.shape[0], W):
                if ck.first:
                    logits = self._launch(
                        self._paged_first_fn(toks.shape[0], W), batch, tbl)
                else:
                    logits = self._launch(
                        self._paged_extend_fn(toks.shape[0], W), tok_d,
                        ck.offset, tbl)
            if not ck.last:
                return None
            with span("backend.fetch"):
                tok = np.asarray(self.sample(logits))
                self.last_tokens[ck.slot] = tok
                self.positions[ck.slot] = ck.offset + toks.shape[0] - 1
            return tok

    def run_decode(self, slots: List[int], page_tables: np.ndarray,
                   audit_pages: Optional[List[int]] = None) -> np.ndarray:
        """One batched decode round over `slots` on the (B, W) compute-page
        table ``page_tables`` (inactive rows all-null; the active mask
        keeps a mid-prefill slot's pages unwritten). ``audit_pages`` lists
        compute pages the round must not write (other sessions' pages).
        Returns the sampled tokens for all B rows."""
        B = self.ecfg.max_slots
        W = int(np.shape(page_tables)[-1])
        with span("backend.decode", width=W, rows=len(slots)) as sp:
            act = np.zeros((B,), bool)
            act[slots] = True
            before = None
            if self.ecfg.audit_decode_masking and audit_pages:
                idx = np.asarray(audit_pages, np.int32)
                before = [np.asarray(leaf[:, idx])
                          for leaf in jax.tree.leaves(self.paged_caches)]
            with span("backend.inputs"):
                # an active row attends keys 0..position + 1
                self._count_kv_pages(
                    sp, "decode", np.asarray(page_tables), 1,
                    np.where(act, self.positions + 2, 0))
                pos = jnp.asarray(np.maximum(self.positions + 1, 0),
                                  jnp.int32)
                tbl = jnp.asarray(self._launch_table(page_tables), jnp.int32)
                W = tbl.shape[-1]
                last = jnp.asarray(self.last_tokens)
                act_d = jnp.asarray(act)
            with span("backend.launch"), self._build_scope("decode", 1, W):
                logits = self._launch(self._paged_decode_fn(W), last, pos,
                                      tbl, act_d)
            if before is not None:
                # the clobbering guard: a decode round writes exactly one
                # row of each active session's open page — shared (sealed)
                # pages and other sessions' pages must come back
                # bit-identical
                idx = np.asarray(audit_pages, np.int32)
                for b, leaf in zip(before,
                                   jax.tree.leaves(self.paged_caches)):
                    after = np.asarray(leaf[:, idx])
                    assert np.array_equal(b, after, equal_nan=True), \
                        "decode wrote another session's compute page " \
                        "(paged masking regression)"
            with span("backend.fetch"):
                next_np = np.asarray(self.sample(logits))
                for slot in slots:
                    self.positions[slot] += 1
                    self.last_tokens[slot] = next_np[slot]
            return next_np

    def free_slot(self, slot: int) -> None:
        self.positions[slot] = -1


# ---------------------------------------------------------------------------
# MemoryPlane: the MRM control-plane half
# ---------------------------------------------------------------------------


def choose_hot_tier(mem: MemorySystem, cfg: ModelConfig,
                    ecfg: EngineConfig) -> Optional[str]:
    """Pick the tier hot (frequently reused) prefix KV should live in, via
    the paper-§4 placement solver: a read-heavy, rarely-rewritten,
    long-lived data class over the engine's actual tiers. Returns a tier
    *name*, or None when the solve is infeasible. solve_placement speaks
    technology names, so each tier's tech is aliased to its tier name —
    two tiers sharing one technology stay distinguishable."""
    import dataclasses

    from repro.core.memclass import YEAR
    from repro.core.tiering import DataClassProfile, Tier, solve_placement

    tiers = [Tier(tech=dataclasses.replace(d.tech, name=name),
                  capacity_bytes=d.capacity)
             for name, d in mem.devices.items()]
    size = 0.25 * mem.devices[ecfg.kv_tier].capacity
    hot = DataClassProfile(
        name="kv_prefix_hot", size_bytes=size,
        read_bw_bytes_s=size,                        # reread ~once per second
        write_bw_bytes_s=size / ecfg.radix_hot_retention_s,  # rewritten per retention
        lifetime_s=ecfg.radix_hot_retention_s, soft_state=True)
    res = solve_placement([hot], tiers, device_life_s=5 * YEAR)
    if not res.feasible:
        return None
    name = res.assignment["kv_prefix_hot"]
    return name if name in mem.devices else None


class MemoryPlane:
    """Weight regions + paged KV + per-tier step metering. All placement,
    retention and pressure decisions live here; the accounting scale
    (``acct_cfg``) is decoupled from the compute scale."""

    def __init__(self, acct_cfg: ModelConfig, mem: MemorySystem,
                 ecfg: EngineConfig):
        self.cfg = acct_cfg
        self.mem = mem
        self.ecfg = ecfg
        hot_tier = ecfg.radix_hot_tier
        if hot_tier == "auto":
            hot_tier = choose_hot_tier(mem, acct_cfg, ecfg)
        elif hot_tier is not None and hot_tier not in mem.devices:
            raise ValueError(f"radix_hot_tier {hot_tier!r} is not a tier "
                             f"({sorted(mem.devices)})")
        self.hot_tier = hot_tier
        # point stacks' recurrent-state pages ride on their KV pages
        state_bp = float(acct_cfg.state_bytes_per_page())
        self.kv = PagedKVManager(acct_cfg, mem, ecfg.kv_tier,
                                 ecfg.page_tokens, ecfg.expected_session_s,
                                 spill_tier=ecfg.kv_spill_tier,
                                 policy=ecfg.kv_pressure_policy,
                                 high_watermark=ecfg.kv_high_watermark,
                                 hot_threshold=ecfg.radix_hot_threshold,
                                 hot_retention_s=ecfg.radix_hot_retention_s,
                                 hot_tier=hot_tier,
                                 cold_ttl_s=ecfg.radix_cold_ttl_s,
                                 tail_copy=ecfg.tail_copy,
                                 demote_on_pressure=ecfg.demote_on_pressure,
                                 state_bytes_page=state_bp)
        counts = acct_cfg.param_counts()
        self.weight_bytes = counts["total"] * 2  # bf16
        self.active_weight_bytes = counts["active"] * 2
        # deploy weights into the weight tier (written once — §2 of paper)
        self.weight_region = self._deploy()
        self._snap = None

    def _deploy(self) -> Optional[int]:
        return self.mem.write_region(
            self.ecfg.weight_tier, "weights", self.weight_bytes,
            expected_lifetime_s=self.mem.devices[
                self.ecfg.weight_tier].tech.retention_s)

    def redeploy_weights(self) -> None:
        """Model update (paper §2/§3: bulk weight overwrite): release the
        old weight region and write the new one — the wear/endurance
        accounting of Figure 1's weight-update bars, from the system."""
        self.mem.release_region(self.weight_region)
        self.weight_region = self._deploy()

    # -- per-step metering ---------------------------------------------
    def begin_step(self) -> None:
        self._snap = self.mem.snapshot()

    def weight_pass(self) -> None:
        """One model pass streams the active weights from the weight tier."""
        self.mem.read_region(self.weight_region, self.active_weight_bytes)

    def finish_step(self):
        """Per-tier step latency: each tier's traffic at its own read/write
        bandwidth, tiers in parallel -> the slowest bounds the step."""
        return self.mem.step_latency_since(self._snap)

    def report(self) -> dict:
        return self.mem.report()


# ---------------------------------------------------------------------------
# ServeEngine: the orchestrator
# ---------------------------------------------------------------------------


class ServeEngine:
    """One replica's orchestrator: plans each step (prefill chunks + one
    decode round), executes it against the :class:`ComputeBackend` and
    :class:`MemoryPlane`, and advances the simulated clock by the modelled
    per-tier step latency.

    Invariants the tests rely on:

    - **Hit/cold equivalence** — a prefix hit (shared pages spliced into
      the page table + extend from the boundary) and a cross-replica
      migrated hit decode bit-identically (fp32) to a cold start; at least
      one prompt token always computes.
    - **Zero-copy hits** — a hit copies no compute state but the rows of
      a sub-page tail (DESIGN.md §9); the KV tier's metered reads are the
      pages the step programs gather (``kernel_read_bytes``).
    """

    # always on the paged plane: kept for callers that check it, until
    # the configs' ``paged_kernel`` key goes (ROADMAP D2)
    paged = True

    def __init__(self, cfg: ModelConfig, params, mem: MemorySystem,
                 ecfg: EngineConfig, account_cfg: Optional[ModelConfig] = None):
        """``account_cfg`` decouples the memory-accounting scale from the
        compute scale: CPU tests run a reduced model for real token
        generation while the control plane meters the *deployment-size*
        config's weight/KV byte streams (the paper's figures of merit)."""
        self.cfg = cfg
        self.acct_cfg = account_cfg or cfg
        self.params = params
        self.mem = mem
        self.ecfg = ecfg
        if not ecfg.paged_kernel:
            raise ValueError("paged_kernel=False: the paged compute plane "
                             "is the only compute path (DESIGN.md §10)")
        # how this stack resumes on a prefix hit (DESIGN.md §8):
        # "positional" (attention/MLA) or "point" (SSM/hybrid)
        self.snapshot_kind = tfm.snapshot_kind(cfg)
        self.sched = ContinuousBatchScheduler(ecfg.max_slots,
                                              ecfg.max_prefills_per_step)
        self.backend = ComputeBackend(cfg, params, ecfg)
        self.memplane = MemoryPlane(self.acct_cfg, mem, ecfg)
        self.kernel_read_bytes = 0.0   # metered page gathers
        # every memory-plane page owns one compute page for its life — a
        # radix hit shares the Page object, hence the compute page: zero
        # copy bytes
        self.memplane.kv.on_page_alloc = self._on_page_alloc
        self.memplane.kv.on_page_release = self._on_page_release
        # per-layer (kv_bytes_per_token, window, state_bytes) at the
        # accounting scale: the analytic model of the step programs'
        # per-page read stream — positional layers gather token rows,
        # point layers additionally pull one state page per step
        self._acct_layers = []
        state_lb = float(self.acct_cfg.ssm_state_bytes_layer())
        for spec in self.acct_cfg.layer_specs():
            if spec.kind == "mla":
                lb, sb = (self.acct_cfg.kv_lora_rank
                          + self.acct_cfg.qk_rope_dim) * 2, 0.0
            elif spec.kind == "attn":
                lb, sb = (2 * self.acct_cfg.n_kv_heads
                          * self.acct_cfg.resolved_head_dim * 2), 0.0
            elif spec.kind == "hybrid":
                lb = (2 * self.acct_cfg.n_kv_heads
                      * self.acct_cfg.resolved_head_dim * 2)
                sb = state_lb
            else:                      # ssm: no KV token stream
                lb, sb = 0.0, state_lb
            self._acct_layers.append((float(lb), spec.window, sb))
        # fault injection (DESIGN.md §11): age-driven flips over the paged
        # compute plane, sampled against each page's tracked region
        self.faults = None
        if ecfg.inject_rber:
            from repro.core.faults import FaultInjector
            self.faults = FaultInjector(mem, ecfg.inject_rber,
                                        seed=ecfg.inject_seed)
        self.outputs: Dict[int, list] = {}
        self._inflight: Dict[int, _SlotPrefill] = {}  # slot -> chunk state
        self._prep_cache: Dict[int, tuple] = {}  # rid -> (tokens, chunk, key)
        self._submit_wall: Dict[int, float] = {}  # queued rid -> host clock
        self.tokens_generated = 0
        self.steps = 0
        self.prefill_chunks_run = 0
        self.prefill_tokens_computed = 0   # tokens that ran through the model
        self.prefill_tokens_skipped = 0    # tokens a radix hit skipped
        self.prefix_compute_hits = 0       # admissions resumed past a hit

    # -- legacy surface (kept stable for callers/tests) ----------------
    @property
    def kv(self) -> PagedKVManager:
        return self.memplane.kv

    @property
    def weight_bytes(self) -> float:
        return self.memplane.weight_bytes

    @property
    def active_weight_bytes(self) -> float:
        return self.memplane.active_weight_bytes

    @property
    def weight_region(self):
        return self.memplane.weight_region

    @property
    def positions(self):
        return self.backend.positions

    @property
    def last_tokens(self):
        return self.backend.last_tokens

    def redeploy_weights(self) -> None:
        self.memplane.redeploy_weights()

    # ------------------------------------------------------------------
    def submit(self, prompt_tokens: list, max_new_tokens: int,
               migrated_tokens: int = 0, at: Optional[float] = None,
               admit_after: Optional[float] = None) -> int:
        """``migrated_tokens`` marks how many leading tokens a cross-replica
        migration just grafted into this replica's tree for this request —
        the scheduler counts them as a match for prefix-aware admission
        even if the grafted leaf is evicted before the request is picked.

        ``at`` stamps an explicit arrival time (event-driven drivers
        submit from a fleet clock that may be ahead of this replica's);
        ``admit_after`` defers admission — an event-mode migration lands
        its pages at the link's delivery time and the triggering request
        waits for them, so its TTFT pays queue wait + transfer.

        Any prompt length is admissible: there is one unpadded chunked
        path (DESIGN.md §5), and ``_chunk_plan`` splits a long prompt into
        chunks even with ``chunk_tokens=None``."""
        rid = len(self.outputs)
        self.outputs[rid] = []
        self._submit_wall[rid] = mark("request.submit", rid=rid)
        submitted = self.mem.now if at is None else at
        self.sched.submit(Request(rid, prompt_tokens, max_new_tokens,
                                  submitted,
                                  migrated_tokens=migrated_tokens,
                                  admit_after=(submitted if admit_after is None
                                               else admit_after)))
        return rid

    def abandon(self, request_id: int, now: float) -> bool:
        """Drop one queued request by id (event-driven abandonment).
        Returns False if it already left the queue."""
        self._submit_wall.pop(request_id, None)
        return self.sched.abandon(request_id, now)

    # ------------------------------------------------------------------
    def _min_ring_len(self) -> int:
        """Smallest per-layer cache ring (windowed layers have rings of
        cache_len_for(window, max_cache_len) < max_cache_len)."""
        from repro.models.attention import cache_len_for
        return min(cache_len_for(spec.window, self.ecfg.max_cache_len)
                   for spec in self.cfg.layer_specs())

    def _chunk_plan(self, toks: np.ndarray) -> tuple:
        """(tokens, chunk) for a prompt — **never padded** (DESIGN.md §5):
        token i sits at position prefix_len + i for every request, so
        shared prefixes are position-aligned and radix-matchable across
        prompt lengths (the tail chunk compiles per distinct length;
        acceptable for the sim). ``chunk_tokens=None`` means one maximal
        chunk on the same path. Either way the chunk is clamped to the
        smallest per-layer ring — a larger chunk would collide intra-chunk
        ring slots (duplicate scatter indices) — and once the prompt
        overflows the ring it is halved so each extend still sees the
        previous chunks' tail."""
        ecfg = self.ecfg
        L = toks.shape[0]
        min_ring = self._min_ring_len()
        chunk = L if ecfg.chunk_tokens is None else ecfg.chunk_tokens
        chunk = min(chunk, min_ring)
        if L + self.backend.prefix_len() > min_ring:
            chunk = min(chunk, max(16, min_ring // 2))
        return toks, max(1, min(chunk, L))

    def _radix_key(self, toks: np.ndarray) -> np.ndarray:
        """Radix tokens in *position space*: the meta/frontend prefix is a
        run of sentinel tokens shared by every request on this engine, so
        page boundaries in the tree line up with KV page boundaries."""
        plen = self.backend.prefix_len()
        if plen == 0:
            return toks
        sent = np.full((plen,) + toks.shape[1:], -1, toks.dtype)
        return np.concatenate([sent, toks], axis=0)

    def _prep(self, req: Request) -> tuple:
        """(tokens, chunk, radix_key) for a request, memoized while it sits
        in the queue (prefix-aware admission rescoring would otherwise
        rebuild the arrays per scheduling round)."""
        ent = self._prep_cache.get(req.request_id)
        if ent is None:
            toks = np.asarray(req.prompt_tokens, np.int32)
            toks, chunk = self._chunk_plan(toks)
            key = self._radix_key(toks) if self.ecfg.prefix_caching else None
            ent = (toks, chunk, key)
            self._prep_cache[req.request_id] = ent
        return ent

    def radix_key_for(self, prompt_tokens: list) -> Optional[np.ndarray]:
        """Position-space radix key for a raw prompt (sentinel meta prefix
        + unpadded tokens) — the key the tree, the fleet prefix directory
        and cross-replica migration all share. None with prefix caching
        off."""
        if not self.ecfg.prefix_caching:
            return None
        return self._radix_key(np.asarray(prompt_tokens, np.int32))

    def prefix_match_len(self, prompt_tokens: list) -> int:
        """Longest radix-matchable prefix (in position-space tokens) this
        engine holds for `prompt_tokens` — side-effect-free; the cluster
        router and prefix-aware scheduler score with this."""
        key = self.radix_key_for(prompt_tokens)
        return 0 if key is None else self.kv.match_len(key)

    def _compute_reuse(self, match: PrefixMatch, toks: np.ndarray) -> tuple:
        """(tokens of the prompt the compute plane may skip, sub-page tail
        tokens used). The matched pages are the compute state: the hit is
        a page-table splice, and only a sub-page tail copies (page rows,
        DESIGN.md §9). At least one token always runs — the last
        position's logits seed the first sampled token.

        Point stacks (SSM/hybrid) have no mid-page state, so tails stay
        off and resumption is clamped down to the last page boundary: the
        state page there holds the exact boundary state (DESIGN.md §8)."""
        plen = self.backend.prefix_len()
        L = toks.shape[0]
        if match.tokens == 0:
            return 0, 0
        tail = (self.kv.tail_available(match)
                if self.ecfg.tail_copy
                and self.snapshot_kind == "positional" else 0)
        reuse = max(0, min(match.tokens + tail - plen, L - 1))
        # the one-token-always-computes clamp may land the resumption
        # point back inside the tail; only the tokens actually skipped
        # past the page boundary are worth copying
        tail = max(0, min(tail, reuse - (match.tokens - plen)))
        if self.snapshot_kind == "point":
            pt = self.ecfg.page_tokens
            reuse = max(0, ((plen + reuse) // pt) * pt - plen)
        return (reuse, tail) if reuse else (0, 0)

    def _admit(self, slot: int, req: Request) -> _SlotPrefill:
        ecfg = self.ecfg
        toks, chunk, key = self._prep(req)
        self._prep_cache.pop(req.request_id, None)
        match = None
        reuse, tail = 0, 0
        if ecfg.prefix_caching:
            match = self.kv.match_prefix(key)
            reuse, tail = self._compute_reuse(match, toks)
        # point stacks always chunk on the position-space page grid — the
        # partition, not just the tokens, determines the recurrent
        # state's rounding, so warm/cold/migrated runs must all cut
        # prompts the same way (there is only one prompt layout now)
        grid = ecfg.page_tokens if self.snapshot_kind == "point" else None
        st = _SlotPrefill(req=req, tokens=toks, chunk=chunk,
                          key=key, match=match, done=reuse, grid=grid)
        if reuse:
            # the hit is real in the compute plane: the matched pages are
            # spliced into the session's table below, nothing is copied
            self.prefix_compute_hits += 1
            self.prefill_tokens_skipped += reuse
            req.prompt_pos = min(reuse, req.prompt_len)
        # open (and pin) the KV session at admission so matched radix
        # nodes cannot be evicted between planning and execution; the
        # compute-vetted tail is copied into the session's own page there
        self.kv.open_session(req.request_id, match=match, tail_tokens=tail)
        if tail:
            # the memory plane just copied the tail into the session's own
            # open page; mirror it on the compute plane — the ONLY copy a
            # hit performs, and only for mid-page resumption
            src = match.tail_node.pages[0].compute_page
            dst = self.kv.sessions[req.request_id].pages[-1].compute_page
            if src is not None and dst is not None:
                self.backend.copy_page_rows(src, dst, tail)
        self._inflight[slot] = st
        self.sched.mark_prefilling(slot)
        return st

    def _plan_step(self) -> StepPlan:
        """Scheduler half of the step: decide which prefill chunks run and
        which slots decode. In-flight chunked prefills continue first
        (bounding time-to-first-token for admitted requests), then new
        admissions fill the remaining prefill budget — preferring queued
        requests that share a hot prefix (prefix-aware admission)."""
        plan = StepPlan()
        if self.ecfg.abandon_after_s is not None:
            # queued sessions older than the timeout hung up before first
            # token; sweep them before admission so they never take a slot
            for req in self.sched.abandon_timed_out(
                    self.mem.now, self.ecfg.abandon_after_s):
                self._submit_wall.pop(req.request_id, None)
        prefix_len = self.backend.prefix_len()
        budget = self.ecfg.max_prefills_per_step
        for slot in sorted(self._inflight):
            if budget <= 0:
                break
            plan.prefill.append(self._inflight[slot].next_chunk(slot, prefix_len))
            budget -= 1
        if budget > 0:
            match_len = (self._sched_match_len if self.ecfg.prefix_caching
                         else None)
            for slot, req in self.sched.admissions(limit=budget,
                                                   match_len=match_len,
                                                   now=self.mem.now):
                t = self._submit_wall.pop(req.request_id, None)
                if t is not None:
                    recorder().record("request.queued", t,
                                      time.perf_counter(),
                                      rid=req.request_id)
                st = self._admit(slot, req)
                plan.prefill.append(st.next_chunk(slot, prefix_len))
                budget -= 1
        plan.decode = self.sched.decode_slots()
        return plan

    def _sched_match_len(self, req: Request) -> int:
        _, _, key = self._prep(req)
        return self.kv.match_len(key)

    # -- cross-replica migration ----------------------------------------
    def export_prefix(self, key_tokens) -> Optional[dict]:
        """Donor half of a cross-replica prefix migration: match the
        longest published prefix of ``key_tokens`` (position-space), read
        its pages out of this replica's tiers (metered reads — the
        transfer is not free for the donor), and return the page metadata
        and the matched compute pages for the receiver."""
        if not self.ecfg.prefix_caching:
            return None
        # non-bumping walk: a migration probe is not local reuse — it must
        # not feed the donor's hit counts / hot promotion / LRU order (the
        # traffic is being moved AWAY) nor inflate the hit count it exports
        m = self.kv.radix.match(key_tokens, self.mem.now,
                                bump_hits=False, bump_lru=False)
        if m.tokens == 0:
            return None
        kv_bytes = 0.0
        for p in m.pages:
            # point stacks: the page's region also carries its
            # recurrent-state page, which the transfer ships too
            nb = p.n_tokens * self.kv.kv_bytes_token + self.kv.state_bytes_page
            if p.region_id is not None and nb > 0:
                self.mem.read_region(p.region_id, nb, sequential=True)
            kv_bytes += nb
        out = {"tokens": np.asarray(key_tokens)[:m.tokens],
               "n_tokens": m.tokens, "kv_bytes": kv_bytes,
               "hot": m.node.hot, "hits": m.node.hits}
        ids = [p.compute_page for p in m.pages]
        if all(i is not None for i in ids):
            out["page_data"] = self.backend.export_pages(ids)
            out["page_tokens"] = self.kv.page_tokens
        return out

    def import_prefix(self, tokens, hot: bool = False, hits: int = 0,
                      page_data=None,
                      page_tokens: Optional[int] = None) -> dict:
        """Receiver half: adopt the pages (metered writes into this
        replica's tiers; a donor-hot prefix lands in the hot tier with
        long retention — placement re-solved on arrival) and write the
        donor's compute pages ``page_data`` straight into the pool pages
        the adoption allocated — a later local hit on the grafted prefix
        is then a zero-copy page-table splice. Data that does not match
        this replica's page geometry or cache families is rejected
        *before* adoption (a graft this engine cannot compute on would
        poison later hits)."""
        if (page_data is None or page_tokens != self.kv.page_tokens
                or not self.backend.pages_compatible(page_data)):
            return {"new_tokens": 0, "total_tokens": 0}
        new_tokens, total, _ = self.kv.adopt_prefix(tokens, hot=hot,
                                                    hits=hits)
        inserted = self.kv._last_adopt_pages
        if inserted:
            # the graft kept pages [dup, total) — slice the donor data to
            # the pages actually inserted and write them in place
            dup_pages = (total - new_tokens) // self.kv.page_tokens
            ids = [p.compute_page for p in inserted]
            data = jax.tree.map(
                lambda a: a[:, dup_pages:dup_pages + len(ids)], page_data)
            self.backend.import_pages(ids, data)
        return {"new_tokens": new_tokens, "total_tokens": total}

    # -- paged compute plane (DESIGN.md §10) ---------------------------
    def _on_page_alloc(self, page) -> None:
        page.compute_page = self.backend.alloc_page()

    def _on_page_release(self, page) -> None:
        if page.compute_page is not None:
            self.backend.free_page(page.compute_page)
            page.compute_page = None

    def _session_table(self, rid: int) -> np.ndarray:
        """The request's compute-page table, padded with null page 0 to the
        power-of-2 width bucket. Table slot j covers absolute positions
        [j*page_tokens, (j+1)*page_tokens) — shared radix pages appear at
        the same slots for every borrower (zero-copy hit)."""
        pages = self.kv.sessions[rid].pages
        W = self.backend.table_width(len(pages))
        tbl = np.zeros((W,), np.int32)
        for j, p in enumerate(pages):
            tbl[j] = p.compute_page if p.compute_page is not None else 0
        return tbl

    def _decode_tables(self, slots: List[int]) -> tuple:
        """(B, W) compute-page tables for a decode round (inactive rows
        all-null) plus the audit list: compute pages resident in sessions
        that are NOT decoding this round and not shared with one that is —
        a decode write landing there is the paged clobbering class."""
        B = self.ecfg.max_slots
        rows, own = {}, set()
        for slot in slots:
            rid = self.sched.active[slot].request_id
            rows[slot] = self._session_table(rid)
            own.update(int(p) for p in rows[slot])
        W = max(r.shape[0] for r in rows.values()) if rows else 1
        tables = np.zeros((B, W), np.int32)
        for slot, r in rows.items():
            tables[slot, :r.shape[0]] = r
        audit = None
        if self.ecfg.audit_decode_masking:
            audit = sorted({
                int(p.compute_page) for s in self.kv.sessions.values()
                for p in s.pages
                if p.compute_page is not None and p.compute_page not in own})
        return tables, audit

    def _meter_paged_reads(self, rid: int, q0: int, q1: int) -> None:
        """Meter the paged kernel's page-gather read stream for one step of
        request ``rid`` whose queries occupy absolute positions [q0, q1):
        a global layer streams every page up to the last query's page; a
        windowed layer skips pages wholly below every query's window
        (lowest reachable position q0 - window + 1); a point layer pulls
        exactly one recurrent-state snapshot — the previous page's
        boundary state when q0 opens a page, else the open page's running
        state (nothing for an empty history: that read is null page 0).
        Bytes are charged at the accounting scale per layer, against each
        page's actual tier."""
        pages = self.kv.sessions[rid].pages
        pt = self.kv.page_tokens
        hi = -(-q1 // pt)  # pages the kernel gathers: [lo_layer, hi)
        rs = q0 // pt - 1 if q0 % pt == 0 else q0 // pt  # state-read slot
        page_bytes = [0.0] * len(pages)
        for lb, w, sb in self._acct_layers:
            if lb:
                lo = 0 if w is None else max(0, q0 - w + 1) // pt
                for j in range(lo, min(hi, len(pages))):
                    page_bytes[j] += pt * lb
            if sb and 0 <= rs < len(pages):
                page_bytes[rs] += sb
        self.kernel_read_bytes += self.kv.read_pages(rid, page_bytes)

    def _inject_faults(self, slots: List[int]) -> None:
        """Reliability-plane injection point (DESIGN.md §11): before the
        decode kernel gathers its pages, visit every page of every decoding
        session and let the fault injector act on its tracked region's age.
        Near-deadline pages under an active ECC profile scrub-on-read
        (corrected + re-armed, metered through the lifecycle); otherwise
        sampled flips land in the compute page in place, so corruption
        propagates through the real decode math."""
        if self.faults is None:
            return
        protected = getattr(self.mem, "ecc_profile", "off") != "off"
        for slot in slots:
            rid = self.sched.active[slot].request_id
            sess = self.kv.sessions.get(rid)
            if sess is None:
                continue
            for page in sess.pages:
                if page.region_id is None or page.compute_page is None:
                    continue
                r = self.mem.region(page.region_id)
                if r is None:
                    continue
                self.faults.stats.pages_visited += 1
                # scrub-on-read is retention servicing: with --no-refresh
                # (service_refresh=False) the controller neither refreshes
                # nor scrubs, so over-aged corruption lands un-corrected
                if (protected and self.mem.service_refresh
                        and self.faults.wants_scrub(r)):
                    if self.kv.lifecycle.scrub(page):
                        self.faults.note_scrub()
                        continue
                pid = int(page.compute_page)
                data = self.backend.export_pages([pid])
                leaves, treedef = jax.tree.flatten(data)
                hit = False
                out_leaves = []
                for leaf in leaves:
                    flipped, _ = self.faults.corrupt(leaf, r, protected)
                    out_leaves.append(leaf if flipped is None else flipped)
                    hit = hit or flipped is not None
                if hit:
                    self.backend.import_pages(
                        [pid], jax.tree.unflatten(treedef, out_leaves))

    def _account_chunk_kv(self, ck: PrefillChunk) -> None:
        """This chunk's tokens enter the paged KV — unless a shared prefix
        already holds them (prefix reuse is counted once at open)."""
        target = ck.offset + len(ck.tokens)  # kv tokens incl. meta/frontend
        cur = self.kv.sessions[ck.request_id].tokens
        if target > cur:
            self.kv.append_tokens(ck.request_id, target - cur)

    # ------------------------------------------------------------------
    def step(self) -> dict:
        """One engine step: prefill chunks + one decode round, metered.
        Each phase runs under the span that names it (DESIGN.md §3,
        "Telemetry")."""
        with span("engine.step", step=self.steps) as sp:
            with span("engine.plan"):
                plan = self._plan_step()
            sp.attrs["chunks"] = len(plan.prefill)
            sp.attrs["rows"] = len(plan.decode)
            self.memplane.begin_step()
            rpt = StepReport()
            first_token_reqs: List[Request] = []
            for ck in plan.prefill:
                self._run_chunk(ck, rpt, first_token_reqs)
            if plan.decode:
                self._run_decode_round(plan.decode, rpt)
            with span("mrm.finish"):
                # advance simulated time by the modelled step latency
                step_s, per_tier = self.memplane.finish_step()
                self.mem.advance(step_s)
                # the first token is out when the step that computed it
                # completes: TTFT includes this step's modelled latency
                for req in first_token_reqs:
                    req.first_token_at = self.mem.now
                self.kv.maintain()  # cold-leaf decay on the advanced clock
            self.steps += 1
            rpt.step_s = step_s
            rpt.bytes_by_tier = per_tier
            return {"step_s": step_s, "bytes": rpt.bytes,
                    "bytes_by_tier": rpt.bytes_by_tier,
                    "prefill_tokens": rpt.prefill_tokens,
                    "decode_tokens": rpt.decode_tokens,
                    "finished": rpt.finished,
                    "active": len(self.sched.active),
                    "queued": len(self.sched.queue)}

    def _run_chunk(self, ck: PrefillChunk, rpt: StepReport,
                   first_token_reqs: List[Request]) -> None:
        """One prefill chunk (a whole prompt or a piece of one)."""
        with span("engine.prefill", rid=ck.request_id, tokens=len(ck.tokens),
                  offset=ck.offset, first=ck.first):
            st = self._inflight[ck.slot]
            # pages must exist BEFORE compute: the step program writes this
            # chunk's KV into the session's own pages in place
            with span("kv.account"):
                self._account_chunk_kv(ck)
                table = self._session_table(ck.request_id)
            tok = self.backend.run_prefill_chunk(ck, page_table=table)
            with span("mrm.meter"):
                self.memplane.weight_pass()
                # meter the page-gather stream: queries at [q0, q1) — the
                # first chunk embeds the meta prefix, so its oldest query
                # is position 0
                q0 = 0 if ck.first else ck.offset
                self._meter_paged_reads(ck.request_id, q0,
                                        ck.offset + len(ck.tokens))
            self.prefill_chunks_run += 1
            self.sched.stats.prefill_chunks += 1
            st.done += len(ck.tokens)
            st.req.prompt_pos = min(st.done, st.req.prompt_len)
            rpt.prefill_tokens += len(ck.tokens)
            self.prefill_tokens_computed += len(ck.tokens)
            if ck.last:
                req = st.req
                req.prefilled_at = self.mem.now
                first_token_reqs.append(req)
                self.outputs[req.request_id].append(
                    int(np.asarray(tok).flat[0]))
                req.generated += 1
                self.tokens_generated += 1
                if st.key is not None:
                    with span("engine.register"):
                        self.kv.register_prefix(req.request_id, st.key)
                self.sched.mark_decoding(ck.slot)
                del self._inflight[ck.slot]

    def _run_decode_round(self, slots: List[int], rpt: StepReport) -> None:
        """One batched decode round over ``slots``: meter every row's page
        reads, then emit every row's token and release the rows that
        finished (a session is metered before it is closed)."""
        with span("engine.decode", rows=len(slots)):
            with span("kv.account"):
                # the new token's page must exist before the step program
                # writes its KV row in place
                for slot in slots:
                    self.kv.append_tokens(
                        self.sched.active[slot].request_id, 1)
                self._inject_faults(slots)
                tables, audit = self._decode_tables(slots)
            next_np = self.backend.run_decode(slots, page_tables=tables,
                                              audit_pages=audit)
            with span("mrm.meter"):
                self.memplane.weight_pass()
                for slot in slots:
                    # one query at the just-written position
                    p = int(self.backend.positions[slot])
                    self._meter_paged_reads(
                        self.sched.active[slot].request_id, p, p + 1)
            with span("engine.emit"):
                finished: List[int] = []
                for slot in slots:
                    req = self.sched.active[slot]
                    tok = int(np.asarray(next_np[slot]).flat[0])
                    self.outputs[req.request_id].append(tok)
                    req.generated += 1
                    self.tokens_generated += 1
                    rpt.decode_tokens += 1
                    self.sched.stats.decode_tokens += 1
                    if (req.generated >= req.max_new_tokens or
                            (self.cfg.n_codebooks == 1
                             and tok == self.ecfg.eos_token)):
                        finished.append(slot)
                for slot in finished:
                    req = self.sched.finish(slot, self.mem.now)
                    self.kv.close_session(req.request_id)
                    self.backend.free_slot(slot)
                    rpt.finished += 1

    # ------------------------------------------------------------------
    def run_until_idle(self, max_steps: int = 10000,
                       on_stall: str = "raise") -> dict:
        """Step until the scheduler drains. Exhausting ``max_steps`` with
        work still queued/resident is *non-quiescence*: an explicit
        :class:`~repro.serving.events.NonQuiescentError` by default, or —
        with ``on_stall="report"`` — the report with ``quiesced=False``
        (the PR 1–8 behavior silently returned a truncated report)."""
        from repro.serving.events import NonQuiescentError
        start = self.steps
        while not self.sched.idle and self.steps - start < max_steps:
            self.step()
        rep = self.report()
        if not self.sched.idle and on_stall != "report":
            raise NonQuiescentError(
                f"engine not quiescent after {max_steps} steps: "
                f"{len(self.sched.queue)} queued, "
                f"{len(self.sched.active)} resident", rep)
        return rep

    def report(self) -> dict:
        rep = self.memplane.report()
        total_energy = rep["total_energy_j"]
        # steady-state read:write ratio: exclude the one-time model-deploy
        # write (it amortizes to ~0 over a device lifetime — §2.2's >1000:1
        # claim is about the per-token decode stream)
        reads = sum(d.stats.read_bytes for d in self.mem.devices.values())
        writes = sum(d.stats.write_bytes for d in self.mem.devices.values())
        steady_writes = max(writes - self.weight_bytes, 1e-9)
        prefix = self.kv.prefix_report()
        prefix["compute_hits"] = self.prefix_compute_hits
        prefix["tokens_skipped_compute"] = self.prefill_tokens_skipped
        prefix["snapshot_kind"] = self.snapshot_kind
        prefix["hot_tier"] = self.memplane.hot_tier
        return {
            "steps": self.steps,
            "kernel_read_bytes": self.kernel_read_bytes,
            "tokens_generated": self.tokens_generated,
            "finished": self.sched.stats.finished,
            "abandoned": self.sched.stats.abandoned,
            "quiesced": self.sched.idle,
            "pending_requests": len(self.sched.queue) + len(self.sched.active),
            "sim_time_s": self.mem.now,
            "tokens_per_s": self.tokens_generated / max(self.mem.now, 1e-9),
            "energy_per_token_j": total_energy / max(self.tokens_generated, 1),
            "steady_rw_ratio": reads / steady_writes,
            "memory": rep,
            "kv_live_pages": self.kv.live_pages(),
            "dropped_allocs": self.kv.dropped_allocs,
            "pressure": self.kv.pressure_report(),
            "prefill_chunks": self.prefill_chunks_run,
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "prefill_tokens_skipped": self.prefill_tokens_skipped,
            "prefix_hits": self.kv.prefix_hits,
            "prefix_tokens_reused": self.kv.prefix_tokens_reused,
            "prefix": prefix,
            "latency": latency_percentiles(self.sched.latency),
            "reliability": self._reliability_report(),
            "telemetry": self._telemetry_report(),
        }

    def _telemetry_report(self) -> dict:
        """Counters of the work that makes no token (DESIGN.md §3,
        "Telemetry"): step programs built per kind, pool growths, size
        and copies, and the KV pages the attention gathered against those
        it attends per kind, on this engine; spans lost to the ring, and
        XLA compiles (or persistent-cache loads) with their seconds, in
        the process."""
        be = self.backend
        out = {"builds": {k: dict(v) for k, v in be.builds.items()},
               "pool_grows": be.pool_grows,
               "pool_pages": be.pool_pages(),
               "pool_copies": be.pool_copies,
               "kv_pages_gathered": dict(be.kv_pages_gathered),
               "kv_pages_attended": dict(be.kv_pages_attended),
               "attention_kernel_launches": dict(
                   be.attention_kernel_launches)}
        out.update(recorder().totals())
        return out

    def _reliability_report(self) -> dict:
        """The reliability plane's ledger (DESIGN.md §11): ECC profile,
        per-tier check-bit / scrub traffic, and — when injection is on —
        the fault injector's flip/correction/uncorrectable counters."""
        out = {
            "ecc_profile": getattr(self.mem, "ecc_profile", "off"),
            "tiers": {
                n: {"ecc_read_bytes": d.stats.ecc_read_bytes,
                    "ecc_write_bytes": d.stats.ecc_write_bytes,
                    "scrub_read_bytes": d.stats.scrub_read_bytes,
                    "n_scrubs": d.stats.n_scrubs,
                    "scrub_rewrites": d.wear.scrub_rewrites}
                for n, d in self.mem.devices.items()},
        }
        if self.faults is not None:
            out["injection"] = self.faults.stats.as_dict()
            out["inject_rber"] = self.faults.rber
        return out


def latency_percentiles(records: List[dict]) -> dict:
    """TTFT/ITL percentiles over finished-request latency records (the
    cluster frontend pools records across replicas through this too)."""
    out = {"n": len(records)}
    ttft = [r["ttft"] for r in records if r["ttft"] is not None]
    itl = [r["itl"] for r in records if r["itl"] is not None]
    for name, xs in (("ttft", ttft), ("itl", itl)):
        for p in (50, 95, 99):
            out[f"{name}_p{p}"] = (float(np.percentile(xs, p)) if xs else None)
    return out

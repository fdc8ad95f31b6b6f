"""Kernel contract checker (KC1xx) — symbolic BlockSpec/grid/VMEM audit.

Each Pallas kernel in ``repro.kernels`` commits to a *contract*: the grid,
the per-operand block shapes, and the VMEM scratch it allocates for given
logical shapes.  This module mirrors that blocking logic in pure math
(no jax import needed to *check*; only the registry driver imports
``repro.kernels.ops`` for ``TUNABLE_OPS`` drift detection) and audits every
contract against the TPU tiling rules and the Eq.-5 memory budget:

- **KC100** — a ``TUNABLE_OPS`` entry has no contract coverage (the
  checker and the tuning registry drifted apart).
- **KC101** — a block shape does not tile its (padded) array: some array
  dim is not a multiple of the block dim, so the grid either misses or
  double-covers elements.
- **KC102** — lane misalignment: a block's last dim is neither a multiple
  of the 128-wide vector lane nor the full (unsplit) 8-aligned array dim.
- **KC103** — sublane misalignment: a block's second-minor dim is not a
  multiple of the per-dtype sublane tile (f32 8, bf16 16, int8 32) and
  not the full array dim.  A second-minor 1 over a longer array dim is
  refused too (the chip's compiler rejects it; scratch allocations have
  no array and are exempt).
- **KC104** — ssd_scan chunk contract: ``L % chunk != 0`` (the kernel
  asserts this at trace time; here it fails at lint time).
- **KC105** — the working set (sum of all in/out/scratch blocks, the same
  single-counting convention as ``tests/test_kernel_vmem.py``) exceeds
  ``vmem_bytes / 2`` — half of VMEM, leaving Pallas double-buffering
  headroom.  This is the serving-side analogue of the paper's Eq. 5
  "does the working set fit the memory bound" feasibility check.
- **KC106** — GQA head-mapping contract: ``H % KV != 0`` breaks the
  ``h // (H // KV)`` index map shared by the attention kernels.
- **KC107** — 1F1B pipeline-stage contract: some stage's per-chip working
  set (its balanced-cut share of params/grads/optimizer state plus
  ``memory_model.stage_activation_bytes`` — saved activations times the
  stage's in-flight microbatch count) exceeds the Eq.-5 HBM budget.  The
  registry sweep prices each arch at the smallest feasible microbatch
  count and *skips* cells where no count fits (the planner would never
  pick them), so the repo self-run stays clean; the finding fires when a
  pinned pipeline shape is checked directly (``pipeline_stage_findings``).

The registry driver sweeps every arch in ``configs.ARCH_IDS`` against the
paper-scale ``SHAPES`` in bf16 and f32, so a new architecture config that
violates a kernel contract fails lint before it ever reaches a TPU.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.findings import Finding
from repro.configs.base import ARCH_IDS, SHAPES, get_config
from repro.core.hardware import TPU_V5E, Chip

LANE = 128  # minor-dim vector lane width (all dtypes)
SUBLANE = {4: 8, 2: 16, 1: 32}  # dtype bytes -> second-minor tile multiple
DTYPE_NAMES = {4: "f32", 2: "bf16", 1: "int8"}

# op -> the file findings point at (line 0: contract-level, not one line)
KERNEL_FILES = {
    "flash_attention": "src/repro/kernels/flash_attention.py",
    "decode_attention": "src/repro/kernels/decode_attention.py",
    "paged_decode_attention": "src/repro/kernels/decode_attention.py",
    "ssd_scan": "src/repro/kernels/ssd_scan.py",
    "pipeline_stage": "src/repro/distributed/pipeline.py",
}


@dataclasses.dataclass(frozen=True)
class Block:
    """One BlockSpec (or scratch allocation) of a kernel contract."""
    name: str
    shape: Tuple[int, ...]
    dtype_bytes: int
    kind: str  # "in" | "out" | "scratch"
    array_shape: Optional[Tuple[int, ...]] = None  # padded HBM array

    @property
    def nbytes(self) -> int:
        return int(math.prod(self.shape)) * self.dtype_bytes


@dataclasses.dataclass(frozen=True)
class KernelContract:
    op: str
    context: str  # "op:arch:shape:dtype" fingerprint context
    grid: Tuple[int, ...]
    blocks: Tuple[Block, ...]

    @property
    def working_set_bytes(self) -> int:
        return sum(b.nbytes for b in self.blocks)


def _finding(op: str, code: str, msg: str, context: str) -> Finding:
    return Finding(path=KERNEL_FILES[op], line=0, code=code, message=msg,
                   context=context)


# ---------------------------------------------------------------------------
# Contract builders — pure-math mirrors of the kernels' blocking logic
# ---------------------------------------------------------------------------


def _gqa_finding(op: str, H: int, KV: int, context: str) -> List[Finding]:
    if KV <= 0 or H % KV:
        return [_finding(op, "KC106",
                         f"H={H} not divisible by KV={KV}; the "
                         "h // (H // KV) GQA index map is undefined",
                         context)]
    return []


def _flash_geometry(Sq: int, Sk: int, q_block: int, kv_block: int):
    """Blocks clamped to the sequences and the padded lengths, as
    ``kernels.flash_attention.flash_attention`` pads for given blocks."""
    tq = min(q_block, max(Sq, 8))
    tk = min(kv_block, max(Sk, 8))
    return tq, tk, Sq + (-Sq % tq), Sk + (-Sk % tk)


def flash_contract(*, B: int, H: int, KV: int, Sq: int, Sk: int, D: int,
                   Dv: Optional[int] = None, dtype_bytes: int = 2,
                   q_block: int = 512, kv_block: int = 512,
                   context: str = "flash_attention",
                   ) -> Tuple[Optional[KernelContract], List[Finding]]:
    """Mirror of the forward kernel of ``kernels.flash_attention``: q, k, v
    and o as (B, heads, D, S) with the sequence in the lanes (v and o at
    the value head ``Dv``, ``D`` unless given: MLA's is narrower), tq/tk
    clamped to the padded sequence, grid (B, H, nq, nk); q as (tq, D) rows,
    a (tq, Dv) f32 accumulator and lane-replicated running max/sum in
    scratch; the f32 log-sum-exp written as (B, H, 1, Sq) rows."""
    op = "flash_attention"
    bad = _gqa_finding(op, H, KV, context)
    if bad:
        return None, bad
    Dv = Dv or D
    tq, tk, sq_p, sk_p = _flash_geometry(Sq, Sk, q_block, kv_block)
    grid = (B, H, sq_p // tq, sk_p // tk)
    blocks = (
        Block("q", (1, 1, D, tq), dtype_bytes, "in", (B, H, D, sq_p)),
        Block("k", (1, 1, D, tk), dtype_bytes, "in", (B, KV, D, sk_p)),
        Block("v", (1, 1, Dv, tk), dtype_bytes, "in", (B, KV, Dv, sk_p)),
        Block("out", (1, 1, Dv, tq), dtype_bytes, "out", (B, H, Dv, sq_p)),
        Block("lse", (1, 1, 1, tq), 4, "out", (B, H, 1, sq_p)),
        Block("q_rows", (tq, D), dtype_bytes, "scratch"),
        Block("acc", (tq, Dv), 4, "scratch"),
        Block("m_run", (tq, 128), 4, "scratch"),
        Block("l_run", (tq, 128), 4, "scratch"),
    )
    return KernelContract(op, context, grid, blocks), []


def flash_bwd_contracts(*, B: int, H: int, KV: int, Sq: int, Sk: int,
                        D: int, Dv: Optional[int] = None,
                        dtype_bytes: int = 2, q_block: int = 512,
                        kv_block: int = 512, context: str = "flash_attention",
                        ) -> Tuple[List[KernelContract], List[Finding]]:
    """Mirrors of the two backward kernels: dK/dV on grid (B, KV, nk, G,
    nq) with k and v as (tk, D) and (tk, Dv) rows and f32 dK and dV
    accumulators of those shapes in scratch, and dQ on grid (B, H, nq, nk)
    with q and dO as (tq, D) and (tq, Dv) rows and a (tq, D) f32
    accumulator.  Both read (B, heads, D or Dv, S) blocks of q, dO, k, v
    and the f32 log-sum-exp and ``rowsum(dO * O)`` as (1, tq) rows.  Their
    contexts are ``context`` with ``.dkv`` / ``.dq`` after the op."""
    op = "flash_attention"
    bad = _gqa_finding(op, H, KV, context)
    if bad:
        return [], bad
    Dv = Dv or D
    tq, tk, sq_p, sk_p = _flash_geometry(Sq, Sk, q_block, kv_block)
    nq, nk = sq_p // tq, sk_p // tk
    head, sep, rest = context.partition(":")

    def sub(kernel: str) -> str:
        return f"{head}.{kernel}{sep}{rest}"

    q_side = (
        Block("q", (1, 1, D, tq), dtype_bytes, "in", (B, H, D, sq_p)),
        Block("do", (1, 1, Dv, tq), dtype_bytes, "in", (B, H, Dv, sq_p)),
        Block("lse", (1, 1, 1, tq), 4, "in", (B, H, 1, sq_p)),
        Block("delta", (1, 1, 1, tq), 4, "in", (B, H, 1, sq_p)),
    )
    kv_side = (
        Block("k", (1, 1, D, tk), dtype_bytes, "in", (B, KV, D, sk_p)),
        Block("v", (1, 1, Dv, tk), dtype_bytes, "in", (B, KV, Dv, sk_p)),
    )
    dkv = KernelContract(
        op, sub("dkv"), (B, KV, nk, H // KV, nq), q_side + kv_side + (
            Block("dk", (1, 1, D, tk), dtype_bytes, "out", (B, KV, D, sk_p)),
            Block("dv", (1, 1, Dv, tk), dtype_bytes, "out",
                  (B, KV, Dv, sk_p)),
            Block("k_rows", (tk, D), dtype_bytes, "scratch"),
            Block("v_rows", (tk, Dv), dtype_bytes, "scratch"),
            Block("dk_acc", (tk, D), 4, "scratch"),
            Block("dv_acc", (tk, Dv), 4, "scratch"),
        ))
    dq = KernelContract(
        op, sub("dq"), (B, H, nq, nk), q_side + kv_side + (
            Block("dq", (1, 1, D, tq), dtype_bytes, "out", (B, H, D, sq_p)),
            Block("q_rows", (tq, D), dtype_bytes, "scratch"),
            Block("do_rows", (tq, Dv), dtype_bytes, "scratch"),
            Block("dq_acc", (tq, D), 4, "scratch"),
        ))
    return [dkv, dq], []


def decode_contract(*, B: int, H: int, KV: int, S: int, D: int,
                    dtype_bytes: int = 2, kv_block: int = 512,
                    context: str = "decode_attention",
                    ) -> Tuple[Optional[KernelContract], List[Finding]]:
    """Mirror of the linear-cache decode kernel: one query row per (b, h),
    KV streamed in tk-sized blocks, positions scalar-prefetched to SMEM
    (not VMEM-counted)."""
    op = "decode_attention"
    if KV <= 0 or H % KV:
        return None, [_finding(op, "KC106",
                               f"H={H} not divisible by KV={KV}; the "
                               "h // (H // KV) GQA index map is undefined",
                               context)]
    tk = min(kv_block, max(S, 8))
    s_p = S + (-S % tk)
    grid = (B, H, s_p // tk)
    blocks = (
        Block("q", (1, 1, 1, D), dtype_bytes, "in", (B, H, 1, D)),
        Block("k", (1, 1, tk, D), dtype_bytes, "in", (B, KV, s_p, D)),
        Block("v", (1, 1, tk, D), dtype_bytes, "in", (B, KV, s_p, D)),
        Block("out", (1, 1, 1, D), dtype_bytes, "out", (B, H, 1, D)),
        Block("acc", (1, D), 4, "scratch"),
        Block("m_run", (1,), 4, "scratch"),
        Block("l_run", (1,), 4, "scratch"),
    )
    return KernelContract(op, context, grid, blocks), []


def paged_decode_contract(*, B: int, H: int, KV: int, bs: int, nb: int,
                          D: int, n_pool: int = 0, dtype_bytes: int = 2,
                          context: str = "paged_decode_attention",
                          ) -> Tuple[Optional[KernelContract], List[Finding]]:
    """Mirror of the paged decode kernel: grid (B, H, nb), per-step KV
    blocks of one *physical pool block* (bs rows), block table and
    positions scalar-prefetched to SMEM (not VMEM-counted)."""
    op = "paged_decode_attention"
    if KV <= 0 or H % KV:
        return None, [_finding(op, "KC106",
                               f"H={H} not divisible by KV={KV}; the "
                               "h // (H // KV) GQA index map is undefined",
                               context)]
    n_pool = n_pool or B * nb
    grid = (B, H, nb)
    blocks = (
        Block("q", (1, 1, 1, D), dtype_bytes, "in", (B, H, 1, D)),
        Block("k_pool", (1, 1, bs, D), dtype_bytes, "in",
              (n_pool, KV, bs, D)),
        Block("v_pool", (1, 1, bs, D), dtype_bytes, "in",
              (n_pool, KV, bs, D)),
        Block("out", (1, 1, 1, D), dtype_bytes, "out", (B, H, 1, D)),
        Block("acc", (1, D), 4, "scratch"),
        Block("m_run", (1,), 4, "scratch"),
        Block("l_run", (1,), 4, "scratch"),
    )
    return KernelContract(op, context, grid, blocks), []


def ssd_contract(*, B: int, H: int, L: int, P: int, N: int, chunk: int = 256,
                 dtype_bytes: int = 4, context: str = "ssd_scan",
                 ) -> Tuple[Optional[KernelContract], List[Finding]]:
    """Mirror of the SSD chunked scan: grid (B, H, nc) with an
    ``arbitrary`` (sequential) chunk axis carrying the (N, P) state; dt and
    its f32 log decay ride as (B, H, 1, L) rows."""
    op = "ssd_scan"
    q = min(chunk, L)
    if L % q:
        return None, [_finding(op, "KC104",
                               f"L={L} not divisible by chunk={q}; the "
                               "kernel asserts L % chunk == 0", context)]
    grid = (B, H, L // q)
    blocks = (
        Block("x", (1, 1, q, P), dtype_bytes, "in", (B, H, L, P)),
        Block("dt", (1, 1, 1, q), dtype_bytes, "in", (B, H, 1, L)),
        Block("log_decay", (1, 1, 1, q), 4, "in", (B, H, 1, L)),
        Block("b", (1, q, N), dtype_bytes, "in", (B, L, N)),
        Block("c", (1, q, N), dtype_bytes, "in", (B, L, N)),
        Block("y", (1, 1, q, P), dtype_bytes, "out", (B, H, L, P)),
        Block("h_out", (1, 1, N, P), dtype_bytes, "out", (B, H, N, P)),
        Block("state", (N, P), 4, "scratch"),
    )
    return KernelContract(op, context, grid, blocks), []


# ---------------------------------------------------------------------------
# Contract checks
# ---------------------------------------------------------------------------


def check_contract(c: KernelContract,
                   chip: Chip = TPU_V5E) -> List[Finding]:
    out: List[Finding] = []
    if any(g <= 0 for g in c.grid):
        out.append(_finding(c.op, "KC101",
                            f"degenerate grid {c.grid}", c.context))
    for b in c.blocks:
        arr = b.array_shape
        if arr is not None:
            if len(arr) != len(b.shape):
                out.append(_finding(
                    c.op, "KC101",
                    f"{b.name}: block rank {len(b.shape)} != array rank "
                    f"{len(arr)}", c.context))
                continue
            for i, (blk_d, arr_d) in enumerate(zip(b.shape, arr)):
                if blk_d <= 0 or arr_d % blk_d:
                    out.append(_finding(
                        c.op, "KC101",
                        f"{b.name}: block {b.shape} does not tile array "
                        f"{arr} (dim {i}: {arr_d} % {blk_d} != 0)",
                        c.context))
                    break
        if len(b.shape) < 2:
            continue  # 1-D scratch vectors are not tile-constrained
        lane = b.shape[-1]
        full_lane = arr is not None and lane == arr[-1]
        lane_ok = (lane % LANE == 0
                   or (full_lane and (lane % 8 == 0 or arr[-1] < 8))
                   or (arr is None and lane % 8 == 0))
        if not lane_ok:
            out.append(_finding(
                c.op, "KC102",
                f"{b.name}: last dim {lane} of block {b.shape} is neither "
                f"a multiple of the {LANE}-wide lane nor the full "
                "8-aligned array dim", c.context))
        sub = b.shape[-2]
        mult = SUBLANE.get(b.dtype_bytes, 8)
        full_sub = arr is not None and sub == arr[-2]
        if arr is not None and not (sub % mult == 0 or full_sub):
            out.append(_finding(
                c.op, "KC103",
                f"{b.name}: second-minor dim {sub} of block {b.shape} is "
                f"not a multiple of the {b.dtype_bytes}-byte sublane tile "
                f"({mult}) nor the full array dim", c.context))
    budget = int(chip.vmem_bytes) // 2
    ws = c.working_set_bytes
    if ws > budget:
        out.append(_finding(
            c.op, "KC105",
            f"working set {ws} B exceeds the Eq.-5 VMEM budget "
            f"{budget} B (= vmem_bytes/2, double-buffering headroom) on "
            f"{chip.name if hasattr(chip, 'name') else 'chip'}", c.context))
    return out


# ---------------------------------------------------------------------------
# Registry sweep — every TUNABLE_OPS entry x every arch that exercises it
# ---------------------------------------------------------------------------


def registry_contracts(
    *, dtypes: Sequence[int] = (2, 4), batch: int = 1, kv_block: int = 16,
) -> Tuple[List[KernelContract], List[Finding], Dict[str, List[str]]]:
    """Build contracts for every (op, arch, shape, dtype) combination the
    config registry implies.  ``kv_block`` is the serving pool block size
    (the ``JobSpec.kv_block`` default).  Returns (contracts, builder
    findings, audit) where audit maps op -> the contexts it was checked
    under — the acceptance hook that every tunable op faces >= 2 configs.
    """
    # the kernel's own block choice (imports jax, as the drift guard does)
    from repro.kernels.flash_attention import block_sizes

    contracts: List[KernelContract] = []
    findings: List[Finding] = []
    audit: Dict[str, List[str]] = {}

    def add(op, built):
        c, fs = built
        findings.extend(fs)
        if c is not None:
            contracts.append(c)
        ctx = (c.context if c is not None else
               (fs[0].context if fs else op))
        audit.setdefault(op, []).append(ctx)

    for arch in ARCH_IDS:
        cfg = get_config(arch)
        if cfg.has_attention:
            H, KV, D, Dv = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                            cfg.head_dim)
            if cfg.is_mla:
                # full-sequence MLA: per-head q/k of qk_nope + qk_rope and
                # a narrower value head
                D = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                Dv = cfg.v_head_dim
                # absorbed MLA decode: one shared latent "KV head" of
                # width kv_lora_rank + qk_rope_head_dim (576 for
                # deepseek-v2) — the wide-lane case KC102 must admit
                dec_kv, dec_d = 1, cfg.kv_cache_width
            else:
                dec_kv, dec_d = KV, D
            for shape in ("train_4k", "prefill_32k"):
                s = SHAPES[shape].seq_len
                (tq, tk), (bq, bk) = block_sizes(s, s, D)
                for db in dtypes:
                    ctx = f"flash_attention:{arch}:{shape}:{DTYPE_NAMES[db]}"
                    add("flash_attention",
                        flash_contract(B=batch, H=H, KV=KV, Sq=s, Sk=s,
                                       D=D, Dv=Dv, dtype_bytes=db,
                                       q_block=tq, kv_block=tk, context=ctx))
                    bwd, fs = flash_bwd_contracts(
                        B=batch, H=H, KV=KV, Sq=s, Sk=s, D=D, Dv=Dv,
                        dtype_bytes=db, q_block=bq, kv_block=bk,
                        context=ctx)
                    for c in bwd:
                        add("flash_attention", (c, fs))
            for shape in ("decode_32k", "long_500k"):
                s = SHAPES[shape].seq_len
                for db in dtypes:
                    ctx = f"decode_attention:{arch}:{shape}:{DTYPE_NAMES[db]}"
                    add("decode_attention",
                        decode_contract(B=batch, H=H, KV=dec_kv, S=s,
                                        D=dec_d, dtype_bytes=db,
                                        context=ctx))
            s = SHAPES["decode_32k"].seq_len
            nb = s // kv_block
            for db in dtypes:
                ctx = (f"paged_decode_attention:{arch}:decode_32k:"
                       f"{DTYPE_NAMES[db]}")
                add("paged_decode_attention",
                    paged_decode_contract(B=batch, H=H, KV=dec_kv,
                                          bs=kv_block, nb=nb, D=dec_d,
                                          n_pool=2 * batch * nb,
                                          dtype_bytes=db, context=ctx))
        if cfg.has_ssm:
            for shape in ("train_4k", "prefill_32k"):
                s = SHAPES[shape].seq_len
                for db in dtypes:
                    ctx = f"ssd_scan:{arch}:{shape}:{DTYPE_NAMES[db]}"
                    add("ssd_scan",
                        ssd_contract(B=batch, H=cfg.ssm_heads, L=s,
                                     P=cfg.ssm_head_dim, N=cfg.ssm_state,
                                     chunk=cfg.ssm_chunk, dtype_bytes=db,
                                     context=ctx))
    return contracts, findings, audit


def check_registry(chip: Chip = TPU_V5E, **kw
                   ) -> Tuple[List[Finding], Dict[str, List[str]]]:
    """The analyzer entry point: sweep the registry, check every contract,
    and flag any TUNABLE_OPS entry the sweep never covered (KC100)."""
    contracts, findings, audit = registry_contracts(**kw)
    for c in contracts:
        findings.extend(check_contract(c, chip))
    try:  # drift guard against the tuning registry (imports jax)
        from repro.kernels.ops import TUNABLE_OPS
    except Exception:  # pragma: no cover - jax always importable in-repo
        TUNABLE_OPS = tuple(KERNEL_FILES)
    for op in TUNABLE_OPS:
        if not audit.get(op):
            findings.append(_finding(
                op if op in KERNEL_FILES else "flash_attention", "KC100",
                f"TUNABLE_OPS entry {op!r} has no kernel-contract coverage",
                f"registry:{op}"))
    return findings, audit


# ---------------------------------------------------------------------------
# KC107 — 1F1B pipeline-stage working set vs the Eq.-5 HBM budget
# ---------------------------------------------------------------------------


def pipeline_stage_findings(cfg, shape, *, pipe: int, n_microbatch: int,
                            dp: int, tp: int = 1, attn_impl: str = "flash",
                            remat: str = "block", chip: Chip = TPU_V5E,
                            frac: float = 0.9,
                            context: str = "pipeline_stage") -> List[Finding]:
    """Check every 1F1B stage of a pinned pipeline shape: the stage's
    balanced-cut share of params/grads/optimizer state plus its peak
    activation working set (``stage_activation_bytes``: in-flight
    microbatches scale with ``min(pipe - s, m)``) must fit
    ``frac * hbm_bytes``.  Emits one KC107 per violating stage."""
    # lazy: memory_model reaches repro.models (jax) — same rule as the
    # TUNABLE_OPS drift guard, the pure checkers above stay import-light
    from repro.core.memory_model import n_params, stage_activation_bytes
    from repro.core.pipeline import balanced_stage_cut

    op = "pipeline_stage"
    cycles = ((cfg.num_layers - cfg.first_k_dense)
              // max(len(cfg.pattern), 1))
    if pipe < 1 or cycles < pipe:
        return [_finding(op, "KC107",
                         f"pipe={pipe} does not cut {cycles} layer cycles "
                         "into non-empty stages", context)]
    cut = balanced_stage_cut(cycles, pipe)
    N = n_params(cfg)
    chips = dp * tp
    # per-stage static share (train_memory's conventions: bf16 + fp32
    # master weights, fp32 grads, ZeRO-1 adamw state)
    static = ((2 * N / tp + 4 * N / chips) + 4 * N / tp + 8 * N / chips) / pipe
    budget = frac * chip.hbm_bytes
    out: List[Finding] = []
    for s in range(pipe):
        act = stage_activation_bytes(
            cfg, shape, dp=dp, tp=tp, pipe=pipe, n_microbatch=n_microbatch,
            stage=s, stage_cycles=cut[s + 1] - cut[s], attn_impl=attn_impl,
            remat=remat, seq_parallel=True)
        ws = static + act
        if ws > budget:
            out.append(_finding(
                op, "KC107",
                f"stage {s}/{pipe} working set {ws:.3g} B (static "
                f"{static:.3g} + activations {act:.3g}, "
                f"{min(pipe - s, max(n_microbatch, pipe))} microbatches in "
                f"flight) exceeds the Eq.-5 budget {budget:.3g} B "
                f"(= {frac} * hbm)", context))
    return out


def check_pipeline_registry(chip: Chip = TPU_V5E, *, world: int = 8,
                            shapes: Sequence[str] = ("train_4k",),
                            ) -> Tuple[List[Finding], Dict[str, List[str]]]:
    """Registry sweep for KC107: for every arch x pipe in {2, 4} x shape,
    derive the smallest microbatch count in {p, 2p, 4p} the Eq.-5 gate
    (``memory_model.train_memory``, the planner's own feasibility check)
    accepts.  Cells the gate rejects at every count are *skipped* — the
    planner would never pick them, so they are not lint findings.  A
    gate-accepted cell whose per-stage audit still flags means this
    mirror and ``memory_model`` drifted apart — that surfaces as KC107."""
    from repro.core.memory_model import train_memory

    findings: List[Finding] = []
    audit: Dict[str, List[str]] = {"pipeline_stage": []}
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        cycles = ((cfg.num_layers - cfg.first_k_dense)
                  // max(len(cfg.pattern), 1))
        for pipe in (2, 4):
            if cycles < pipe or world % pipe:
                continue
            dp = world // pipe
            for shape_name in shapes:
                shape = SHAPES[shape_name]
                ctx = f"pipeline_stage:{arch}:{shape_name}:p{pipe}"
                # doubling microbatch counts up to one row per microbatch
                # (more microbatches shrink the in-flight slice, so the
                # smallest feasible m is the tightest cell worth auditing)
                b_rep = max(shape.global_batch // (world // pipe), 1)
                candidates = []
                m = pipe
                while m <= max(b_rep, pipe):
                    candidates.append(m)
                    m *= 2
                for m in candidates:
                    # microbatch=0: the 1F1B rows-per-microbatch derive
                    # from m, the same convention stage_activation_bytes
                    # prices — the gate and the audit see one schedule
                    mem = train_memory(
                        cfg, shape, dp=dp, tp=1, fsdp=False, microbatch=0,
                        attn_impl="flash", remat="block", seq_parallel=True,
                        pipe=pipe, n_microbatch=m)
                    if mem.total > 0.9 * chip.hbm_bytes:
                        continue  # Eq.-5 gate rejects: planner skips too
                    audit["pipeline_stage"].append(f"{ctx}:m{m}")
                    findings.extend(pipeline_stage_findings(
                        cfg, shape, pipe=pipe, n_microbatch=m, dp=dp,
                        chip=chip, context=f"{ctx}:m{m}"))
                    break  # smallest feasible m prices the cell
    return findings, audit


def analyze(root=None) -> List[Finding]:
    """Uniform analyzer interface for the CLI (root unused: contracts come
    from the imported registry, not from file paths)."""
    findings, _ = check_registry()
    pipe_findings, _ = check_pipeline_registry()
    return findings + pipe_findings

"""Hardware constants and cluster topology — the paper's symbol table.

Every symbol of §3 that names a hardware quantity reads off one of these
classes (units noted per field; see ``docs/paper_map.md`` for the full
equation-to-module map):

- ``Chip.hbm_bytes``  -> Eq. (5)'s device memory ``M_GPU``        [bytes]
- ``Chip.peak_flops`` -> the ``T_C`` denominator in the step-time
  roofline (``planner.estimate_step_time``)                       [FLOP/s]
- ``Tier.bw``         -> Lemma 3.2's server bandwidth ``B_ps`` and the
  collective wire bandwidth, per interconnect tier                [bytes/s]
- ``Tier.latency``    -> the per-phase constant added to each collective
  hop at that tier                                                [s]

Two layers:

1. :class:`Chip` — the accelerator itself (TPU v5e-class reproduction
   target, plus the paper's 2017 evaluation hardware, AWS P2 / NVIDIA K80).
   Datasheet constants; :meth:`Chip.scaled` produces the *calibrated*
   overlay (``repro.core.autotune`` replaces peak FLOP/s and HBM bandwidth
   with measured ones, marking the chip name with ``+cal``).
2. :class:`ClusterSpec` — *where the chips sit*: a hierarchy of
   :class:`Tier` levels (chip -> node -> cluster), each with its own
   bandwidth/latency and fan-out.  The paper's guidelines (how many GPUs,
   how many parameter servers, which sync algorithm) are priced against a
   heterogeneous interconnect — PCIe/NVLink inside a node vs Ethernet/IB
   across nodes — and FireCaffe-style reduction trees only pay off when the
   cost model can see that hierarchy.  Every planner/collective consumer
   reads bandwidths through a ``ClusterSpec`` now; the old scalar
   ``chip.link_bw`` survives only as the bandwidth of a single-tier
   ("flat") cluster.

:class:`MeshSpec` keeps the logical mesh geometry (dp x tp) and gains an
optional ``topology``; omitting it yields a flat single-tier cluster
equivalent to the old behaviour.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Tuple


@dataclass(frozen=True)
class Chip:
    name: str
    peak_flops: float  # FLOP/s at the training dtype
    hbm_bytes: float
    hbm_bw: float  # bytes/s
    link_bw: float  # bytes/s per ICI/interconnect link
    vmem_bytes: float = 0.0
    device_kind: str = ""  # jax.Device.device_kind of this chip ("" = none)

    CAL_SUFFIX = "+cal"

    def scaled(self, *, peak_flops: Optional[float] = None,
               hbm_bw: Optional[float] = None,
               link_bw: Optional[float] = None) -> "Chip":
        """A *calibrated* overlay of this chip: same identity, datasheet
        constants replaced by measured ones (``repro.core.autotune``).
        The name gains a ``+cal`` marker so plans priced on measurements
        are distinguishable from datasheet plans."""
        name = (self.name if self.name.endswith(self.CAL_SUFFIX)
                else self.name + self.CAL_SUFFIX)
        return replace(
            self, name=name,
            peak_flops=peak_flops if peak_flops else self.peak_flops,
            hbm_bw=hbm_bw if hbm_bw else self.hbm_bw,
            link_bw=link_bw if link_bw else self.link_bw)

    @property
    def calibrated(self) -> bool:
        return self.name.endswith(self.CAL_SUFFIX)


TPU_V5E = Chip(
    name="tpu-v5e",
    peak_flops=197e12,  # bf16
    hbm_bytes=16 * 2**30,
    hbm_bw=819e9,
    link_bw=50e9,
    vmem_bytes=128 * 2**20,
    device_kind="TPU v5 lite",
)

# Paper-era: NVIDIA GK210 (one half of a K80), AWS P2 instances (Table 1)
K80_GK210 = Chip(
    name="k80-gk210",
    peak_flops=2.91e12,  # fp32 with boost off ~2.9 TFLOP/s
    hbm_bytes=12 * 2**30,
    hbm_bw=240e9,
    link_bw=10e9 / 8,  # 10 Gbit Ethernet (p2.8xlarge "network" as PS link)
)

# chips a JAX device can be, keyed by what the device reports
CHIPS_BY_KIND: Dict[str, Chip] = {TPU_V5E.device_kind: TPU_V5E}


def chip_for_kind(device_kind: str) -> Chip:
    """The :class:`Chip` describing a device of this ``device_kind``
    (``jax.devices()[0].device_kind``).  An unknown kind is an error: a
    plan priced on another chip's constants would be silently wrong."""
    try:
        return CHIPS_BY_KIND[device_kind]
    except KeyError:
        raise KeyError(f"no Chip describes device_kind {device_kind!r}; "
                       f"known: {sorted(CHIPS_BY_KIND)}") from None


# ---------------------------------------------------------------------------
# Topology: tiers of the interconnect hierarchy
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Tier:
    """One level of the interconnect hierarchy.

    ``size`` is the fan-out at this level: the innermost tier groups
    ``size`` chips into a node; the next tier groups ``size`` nodes, and so
    on.  ``bw`` is bytes/s available to each chip for traffic crossing
    *this* tier's links (ICI/NVLink in-node, Ethernet/IB/DCN across).
    """

    name: str
    size: int
    bw: float  # bytes/s per chip across this tier's links
    latency: float = 0.0  # seconds per collective phase at this tier

    def __post_init__(self):
        if self.size < 1:
            raise ValueError(f"tier {self.name!r}: size must be >= 1")
        if self.bw <= 0:
            raise ValueError(f"tier {self.name!r}: bw must be > 0")
        if self.latency < 0:
            raise ValueError(f"tier {self.name!r}: latency must be >= 0")


@dataclass(frozen=True)
class ClusterSpec:
    """A hierarchy of tiers, innermost first (chip -> node -> cluster).

    ``tiers[0]`` groups chips, ``tiers[1]`` groups the resulting nodes, ...
    The total chip count is the product of the tier sizes.
    """

    name: str
    chip: Chip = TPU_V5E
    tiers: Tuple[Tier, ...] = (Tier("pod", 1, TPU_V5E.link_bw),)

    def __post_init__(self):
        if not self.tiers:
            raise ValueError("ClusterSpec needs at least one tier")
        object.__setattr__(self, "tiers", tuple(self.tiers))

    # -- geometry ----------------------------------------------------------
    @property
    def n_chips(self) -> int:
        return math.prod(t.size for t in self.tiers)

    @property
    def tier_sizes(self) -> Tuple[int, ...]:
        return tuple(t.size for t in self.tiers)

    @property
    def tier_bws(self) -> Tuple[float, ...]:
        return tuple(t.bw for t in self.tiers)

    @property
    def uniform(self) -> bool:
        """True when there is no bandwidth hierarchy to exploit: at most
        one tier actually spans more than one group (the flat-mesh case)."""
        return sum(1 for t in self.tiers if t.size > 1) <= 1

    @property
    def min_bw(self) -> float:
        """Bandwidth of the narrowest *spanning* tier (size > 1); this is
        what a flat (topology-blind) collective is priced at."""
        spanning = [t.bw for t in self.tiers if t.size > 1]
        return min(spanning) if spanning else self.tiers[0].bw

    @property
    def bottleneck_tier(self) -> str:
        spanning = [t for t in self.tiers if t.size > 1] or list(self.tiers)
        return min(spanning, key=lambda t: t.bw).name

    def tier(self, name: str) -> Tier:
        for t in self.tiers:
            if t.name == name:
                return t
        raise KeyError(f"no tier {name!r} in cluster {self.name!r}; "
                       f"tiers: {[t.name for t in self.tiers]}")

    def dp_view(self, dp: int, tp: int) -> Tuple[Tier, ...]:
        """The tiers as seen by the data axis when ``tp`` model-parallel
        ranks are packed into the innermost tiers first (the standard
        placement: TP wants the fastest links).  Consumes ``tp`` from the
        inside out and returns the residual per-tier dp fan-out."""
        if dp * tp != self.n_chips:
            raise ValueError(f"dp*tp = {dp * tp} != n_chips = {self.n_chips} "
                             f"for cluster {self.name!r}")
        out: List[Tier] = []
        rem_tp = tp
        for t in self.tiers:
            take = math.gcd(t.size, rem_tp)
            rem_tp //= take
            out.append(replace(t, size=t.size // take))
        if rem_tp != 1:  # tp does not factor along tiers: flat fallback
            return (Tier(self.bottleneck_tier, dp, self.min_bw),)
        return tuple(out)

    # -- serialization (Plan carries this instead of a scalar link_bw) -----
    def to_dict(self) -> Dict:
        return {
            "name": self.name,
            "chip": self.chip.name,
            "tiers": [{"name": t.name, "size": t.size, "bw": t.bw,
                       "latency": t.latency} for t in self.tiers],
        }

    @classmethod
    def from_dict(cls, d: Dict) -> "ClusterSpec":
        chips = {c.name: c for c in (TPU_V5E, K80_GK210)}
        chip_name = d.get("chip", TPU_V5E.name)
        # calibrated overlays serialize as "<chip>+cal"; the measured
        # constants live in the tier bandwidths / the calibration cache, so
        # deserialization falls back to the datasheet base chip
        if chip_name.endswith(Chip.CAL_SUFFIX):
            chip_name = chip_name[:-len(Chip.CAL_SUFFIX)]
        if chip_name not in chips:
            raise KeyError(f"unknown chip {chip_name!r} in serialized "
                           f"cluster {d.get('name')!r}; known: {sorted(chips)}")
        return cls(
            name=d["name"],
            chip=chips[chip_name],
            tiers=tuple(Tier(t["name"], int(t["size"]), float(t["bw"]),
                             float(t.get("latency", 0.0)))
                        for t in d["tiers"]),
        )

    @classmethod
    def flat(cls, chips: int, bw: Optional[float] = None, *,
             chip: Chip = TPU_V5E, name: str = "") -> "ClusterSpec":
        """Single-tier cluster — exactly the pre-topology mesh model."""
        return cls(name=name or f"flat{chips}", chip=chip,
                   tiers=(Tier("pod", chips, bw or chip.link_bw),))


@dataclass(frozen=True)
class MeshSpec:
    """Mesh geometry (dp x tp) + the cluster topology it maps onto."""

    chips: int
    dp: int  # data-parallel degree (pod*data)
    tp: int  # model-parallel degree
    chip: Chip = TPU_V5E
    topology: Optional[ClusterSpec] = None  # None => flat single tier
    # (inter-pod DCN bandwidth lives on the topology's tier now — see
    # MULTI_POD's "dcn" tier — not on a scalar mesh field)

    @property
    def total_flops(self) -> float:
        return self.chips * self.chip.peak_flops

    @property
    def total_hbm(self) -> float:
        return self.chips * self.chip.hbm_bytes

    @property
    def cluster(self) -> ClusterSpec:
        """The topology — or its flat single-tier equivalent when omitted
        (backward compatibility with the scalar-``link_bw`` model)."""
        if self.topology is not None:
            return self.topology
        return ClusterSpec.flat(self.chips, self.chip.link_bw, chip=self.chip)

    @classmethod
    def from_cluster(cls, cluster: ClusterSpec, *, tp: int = 1) -> "MeshSpec":
        n = cluster.n_chips
        if n % tp:
            raise ValueError(f"tp={tp} does not divide {n} chips")
        return cls(chips=n, dp=n // tp, tp=tp, chip=cluster.chip,
                   topology=cluster)


SINGLE_POD = MeshSpec(chips=256, dp=16, tp=16)
MULTI_POD = MeshSpec(
    chips=512, dp=32, tp=16,
    topology=ClusterSpec(
        "2pod-dcn", TPU_V5E,
        (Tier("pod", 256, TPU_V5E.link_bw), Tier("dcn", 2, 25e9))))


# ---------------------------------------------------------------------------
# Named clusters (JobSpec.topology / Session.sweep address these by name)
# ---------------------------------------------------------------------------

CLUSTERS: Dict[str, ClusterSpec] = {
    # flat N-chip meshes: the pre-topology behaviour, spelled explicitly
    "flat8": ClusterSpec.flat(8, name="flat8"),
    "flat16": ClusterSpec.flat(16, name="flat16"),
    # 2 nodes x 4 chips: fast ICI in-node, 20 Gbit/s-class Ethernet across —
    # the acceptance-criteria topology where hierarchy starts to matter
    "2x4": ClusterSpec("2x4", TPU_V5E,
                       (Tier("node", 4, TPU_V5E.link_bw),
                        Tier("cluster", 2, 2.5e9))),
    # 4 nodes x 4 chips over 100 Gbit InfiniBand-class links
    "4x4-ib": ClusterSpec("4x4-ib", TPU_V5E,
                          (Tier("node", 4, TPU_V5E.link_bw),
                           Tier("cluster", 4, 12.5e9))),
    # paper-era: 2 x p2.8xlarge (8 GK210s behind PCIe, 10 GbE between)
    "p2-2x8": ClusterSpec("p2-2x8", K80_GK210,
                          (Tier("node", 8, 10e9),
                           Tier("cluster", 2, 10e9 / 8))),
    # the default pods, addressable by name for sweeps
    "pod": ClusterSpec.flat(256, name="pod"),
    "2pod-dcn": MULTI_POD.topology,
}


def get_cluster(name: str) -> ClusterSpec:
    try:
        return CLUSTERS[name]
    except KeyError:
        raise KeyError(f"unknown cluster {name!r}; known: "
                       f"{sorted(CLUSTERS)}") from None

"""Multi-chip sharded execution: the bank axis stretched across devices.

The paper scales bulk-bitwise throughput by running one broadcast AAP
sequence on many banks at once (`core.bankgroup`); the follow-up in-DRAM
bulk-bitwise execution engine (Seshadri & Mutlu, 2019) extends the same
argument across chips and ranks — every chip adds buses, banks, and sense
amplifiers, so throughput scales with the number of chips as long as
operands never cross a chip boundary. `ChipCluster` is that layer:

  * a bulk operand's words are partitioned over ``max_chips * n_banks``
    **slots** (`shard_words`, the two-level generalization of
    `bankgroup.shard_words`): leading axes ``(n_chips, local_banks)``,
    where the chip axis is laid onto a JAX device mesh via the
    ``"chip"``/``"bank"`` logical rules of `dist.sharding` and the bank
    axis stays chip-local;
  * programs execute under `shard_map`: every chip runs the lowered
    register-machine VM (`core.lowering`, or the Pallas megakernel) over
    its local ``(local_banks, ..., words)`` plane block — one broadcast
    opcode table, per-chip data, zero cross-chip traffic during compute;
  * result readout is **gather-free per shard**: output rows come back
    still sharded over the chip mesh (``out_specs`` keep the chip axis),
    and reductions (`popcounts`) run as a recursive-doubling **tree psum**
    over the chip axis, so only scalars ever cross chips.

The placement granularity is fixed at creation: words are padded to
``max_chips * n_banks`` slots regardless of the *current* chip count, so an
elastic rescale (service layer, `dist.elastic.plan_rescale`) is a pure
re-layout — a chip cluster of C chips sweeps ``max_chips // C`` slot groups
sequentially (the `sweeps` of the rescale plan's ``grad_accum``), and the
bits held by every slot are invariant across rescales.

Everything runs on forced host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) bit-identically to
the single-chip oracle (tests/test_cluster.py, tests/test_property_cluster.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.core import bankgroup, lowering
from repro.core.commands import Program
from repro.core.engine import BuddyError, RowState, _check_outputs
from repro.core.timing import DDR3_1600, DramTiming
from repro.dist.sharding import CLUSTER_RULES, resolve_spec
from repro.obs.telemetry import get_telemetry

CHIP_AXIS = "chip"
DEFAULT_PLACEMENT_CHIPS = 8


def _shard_map(f, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off: bodies mix per-shard
    outputs with tree-psum'd (replicated) scalars, which the varying-axes
    checker cannot type through `ppermute`."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def tree_psum(x: jax.Array, axis_name: str, n: int) -> jax.Array:
    """All-reduce sum over `axis_name` as a recursive-doubling tree.

    log2(n) `ppermute` stages, each pairing shard i with shard i^step —
    the butterfly the 2019 execution engine's inter-chip reduction network
    implements in hardware. Falls back to `lax.psum` when `n` is not a
    power of two (XLA's all-reduce is itself tree-scheduled).
    """
    if n == 1:
        return x
    if n & (n - 1):
        return jax.lax.psum(x, axis_name)
    step = 1
    while step < n:
        perm = [(i, i ^ step) for i in range(n)]
        x = x + jax.lax.ppermute(x, axis_name, perm)
        step *= 2
    return x


class ClusterError(BuddyError):
    pass


@dataclasses.dataclass
class ChipCluster:
    """N chips x M banks as one sharded execution domain.

    ``mesh`` is a 1-D device mesh named `"chip"`; `max_chips * n_banks`
    is the fixed word-slot count every operand is partitioned into
    (`slots`), of which each chip holds ``local_banks = sweeps * n_banks``
    contiguous slot rows. ``n_chips`` must divide ``max_chips`` so the
    re-layout stays a reshape.
    """

    mesh: Mesh
    n_chips: int
    n_banks: int
    max_chips: int

    def __post_init__(self):
        if self.max_chips % self.n_chips:
            raise ClusterError(
                f"n_chips {self.n_chips} must divide placement granularity "
                f"max_chips {self.max_chips}")
        self._exec_cache: Dict[Tuple, object] = {}

    @classmethod
    def create(cls, n_chips: int, n_banks: int = 8,
               max_chips: Optional[int] = None,
               devices: Optional[Sequence] = None) -> "ChipCluster":
        """Build a cluster over the first `n_chips` available devices.

        CI hosts have no accelerators: force multiple host devices with
        ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set before
        jax is imported). `max_chips` defaults to the smallest multiple of
        `n_chips` that is >= 8, so rescales across 1/2/4/8 chips stay pure
        re-layouts of one placement.
        """
        if devices is None:
            devices = jax.devices()
        if n_chips < 1:
            raise ClusterError(f"n_chips must be >= 1, got {n_chips}")
        if len(devices) < n_chips:
            raise ClusterError(
                f"need {n_chips} devices but only {len(devices)} are "
                f"visible; on CPU hosts set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n_chips} before "
                f"importing jax")
        if max_chips is None:
            max_chips = n_chips * math.ceil(DEFAULT_PLACEMENT_CHIPS
                                            / n_chips)
        mesh = Mesh(np.asarray(devices[:n_chips]), (CHIP_AXIS,))
        return cls(mesh=mesh, n_chips=n_chips, n_banks=n_banks,
                   max_chips=max_chips)

    # -- layout --------------------------------------------------------------

    @property
    def sweeps(self) -> int:
        """Sequential slot groups per chip (the rescale plan's accum)."""
        return self.max_chips // self.n_chips

    @property
    def local_banks(self) -> int:
        """Slot rows resident on one chip: sweeps x physical banks."""
        return self.sweeps * self.n_banks

    @property
    def slots(self) -> int:
        """Total word-shard slots; invariant across rescale."""
        return self.max_chips * self.n_banks

    def spec(self, ndim: int):
        """PartitionSpec of a ``(chip, bank, ...)`` tensor on this mesh,
        resolved through the `dist.sharding` logical-axis rules."""
        names = (CHIP_AXIS, "bank") + (None,) * (ndim - 2)
        shape = (self.n_chips, self.local_banks) + (1,) * (ndim - 2)
        return resolve_spec(shape, names, self.mesh, CLUSTER_RULES)

    def sharding(self, ndim: int) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(ndim))

    def shard_words(self, x: jax.Array) -> jax.Array:
        """(..., W) operand -> (n_chips, local_banks, ..., W/slots), with
        the chip axis laid onto the device mesh.

        Words zero-pad up to a multiple of `slots` (zero words are inert
        for every bitwise program; `unshard_words` strips them), so uneven
        word counts shard on every layout.
        """
        s = bankgroup.shard_words(x, self.slots)        # (slots, ..., w)
        s = s.reshape((self.n_chips, self.local_banks) + s.shape[1:])
        return jax.device_put(s, self.sharding(s.ndim))

    def unshard_words(self, x: jax.Array, n_words: int) -> jax.Array:
        """Inverse of `shard_words`: gather shards back to (..., W)."""
        merged = x.reshape((self.slots,) + x.shape[2:])
        return bankgroup.unshard_words(merged, n_words)

    def local_words(self, n_words: int) -> int:
        """Per-slot word count after padding `n_words` to the slot grid."""
        return (n_words + self.slots - 1) // self.slots

    # -- sharded execution ---------------------------------------------------

    def _sharded_vm(self, lp: lowering.LoweredProgram,
                    in_names: Tuple[str, ...], out_names: Tuple[str, ...],
                    shapes: Tuple[Tuple[int, ...], ...], backend: str,
                    mask_ndim: Optional[int]):
        """Jitted shard_map dispatch, memoized per (program, binding).

        ``mask_ndim is None``: returns the output rows still sharded over
        the chip mesh (gather-free readout — ``out_specs`` keep the chip
        axis). Otherwise the body also popcounts each mask-ANDed output
        row and tree-psums the counts over the chip axis, so only
        ``(n_outputs,) + batch`` scalars leave the shards.
        """
        key = (id(lp), in_names, out_names, shapes, backend, mask_ndim)
        hit = self._exec_cache.get(key)
        if hit is not None:
            return hit
        local_words = max(s[-1] for s in shapes)
        in_specs = tuple(self.spec(len(s)) for s in shapes)
        out_ndim = max(len(s) for s in shapes)

        def run_local(vals):
            local = dict(zip(in_names, vals))
            out = lowering.execute_lowered(
                lp, local, row_words=local_words,
                outputs=list(out_names), backend=backend)
            return tuple(out[o] for o in out_names)

        if mask_ndim is None:
            body = run_local
            specs = (in_specs,)
            out_specs = (self.spec(out_ndim),) * len(out_names)
        else:
            def body(vals, mask):
                # fused count epilogue: the VM dispatch popcounts each
                # mask-ANDed output row in place (in VMEM on the pallas
                # backend — no output plane reaches HBM), then the shard
                # dims of (1, local_banks, ...) sum away and the chip
                # axis tree-reduces, keeping any inner batch (query) axes
                per_bank = lowering.execute_lowered(
                    lp, dict(zip(in_names, vals)), row_words=local_words,
                    outputs=list(out_names), backend=backend,
                    reduce="popcount", mask=mask)
                counts = []
                for o in out_names:
                    c = per_bank[o].sum(axis=(0, 1))       # local slots
                    counts.append(tree_psum(c, CHIP_AXIS, self.n_chips))
                return tuple(counts)
            specs = (in_specs, self.spec(mask_ndim))
            out_specs = (resolve_spec((), (), self.mesh, CLUSTER_RULES),
                         ) * len(out_names)
        fn = jax.jit(_shard_map(body, self.mesh, in_specs=specs,
                                out_specs=out_specs))
        if len(self._exec_cache) > 256:
            self._exec_cache.clear()
        self._exec_cache[key] = fn
        return fn

    def run_lowered(self, lp: lowering.LoweredProgram, sharded: RowState,
                    outputs: Sequence[str], backend: str = "scan"
                    ) -> Dict[str, jax.Array]:
        """Execute a lowered program over already-sharded rows.

        Every row of `sharded` carries the (chip, bank) leading axes from
        `shard_words`; returns the requested output rows **still sharded**
        (chip axis intact) — call `unshard_words` only when a flat vector
        is actually needed.

        Wall-span-traced when a tracing telemetry is installed
        process-wide (`repro.obs.set_telemetry`; the scheduler installs
        one per dispatch window).
        """
        tel = get_telemetry()
        if tel.tracing:
            with tel.tracer.span("cluster.run_lowered",
                                 n_chips=self.n_chips, n_banks=self.n_banks,
                                 n_cmds=lp.n_cmds, backend=backend):
                return self._run_lowered(lp, sharded, outputs, backend)
        return self._run_lowered(lp, sharded, outputs, backend)

    def _run_lowered(self, lp: lowering.LoweredProgram, sharded: RowState,
                     outputs: Sequence[str], backend: str
                     ) -> Dict[str, jax.Array]:
        names = tuple(sorted(sharded))
        shapes = tuple(tuple(sharded[k].shape) for k in names)
        fn = self._sharded_vm(lp, names, tuple(outputs), shapes, backend,
                              mask_ndim=None)
        out = fn(tuple(sharded[k] for k in names))
        return dict(zip(tuple(outputs), out))

    def popcounts(self, lp: lowering.LoweredProgram, sharded: RowState,
                  outputs: Sequence[str], mask_shards: jax.Array,
                  backend: str = "scan") -> np.ndarray:
        """Masked popcount of each output row, tree-psum'd across chips.

        `mask_shards` is the catalog tail mask pushed through
        `shard_words` (padding slots are all-zero there, so pad words
        never count); singleton axes are inserted so it broadcasts over
        any inner batch (query) axes. Returns ``(n_outputs,) + batch``
        int counts — the only values that cross the chip boundary.

        Traced like `run_lowered`; the span also records the tree-psum
        reduction depth (``psum_hops`` — recursive doubling over the chip
        axis, `tree_psum`).
        """
        tel = get_telemetry()
        if tel.tracing:
            hops = int(math.ceil(math.log2(self.n_chips))) \
                if self.n_chips > 1 else 0
            with tel.tracer.span("cluster.popcounts",
                                 n_chips=self.n_chips, n_banks=self.n_banks,
                                 n_cmds=lp.n_cmds, backend=backend,
                                 psum_hops=hops):
                return self._popcounts(lp, sharded, outputs, mask_shards,
                                       backend)
        return self._popcounts(lp, sharded, outputs, mask_shards, backend)

    def _popcounts(self, lp: lowering.LoweredProgram, sharded: RowState,
                   outputs: Sequence[str], mask_shards: jax.Array,
                   backend: str) -> np.ndarray:
        names = tuple(sorted(sharded))
        shapes = tuple(tuple(sharded[k].shape) for k in names)
        sample_ndim = max(len(s) for s in shapes)
        mask = mask_shards.reshape(
            mask_shards.shape[:2] + (1,) * (sample_ndim - 3)
            + mask_shards.shape[-1:])
        fn = self._sharded_vm(lp, names, tuple(outputs), shapes, backend,
                              mask_ndim=mask.ndim)
        counts = fn(tuple(sharded[k] for k in names), mask)
        return np.asarray(jnp.stack(counts))

    def execute(self, program: Program, data: RowState,
                outputs: Optional[List[str]] = None,
                backend: str = "scan") -> RowState:
        """Cluster-parallel analog of `bankgroup.execute_banked`.

        Flat (..., W) operand rows are partitioned over chips x banks, the
        program runs once per shard under `shard_map`, and the requested
        outputs come back reassembled to their original width —
        bit-identical to `engine.execute(program, data)` for every
        program, chip count, and backend.
        """
        lp = lowering.lower(program)
        if outputs is not None:
            _check_outputs(outputs, set(lp.row_names) | set(data), program)
        n_words = int(next(iter(data.values())).shape[-1])
        sharded = {k: self.shard_words(jnp.asarray(v, jnp.uint32))
                   for k, v in data.items()}
        if outputs is None:
            out_names = [n for n in lp.row_names if n != lowering.SINK]
            out_names += [k for k in sharded if k not in out_names]
        else:
            out_names = list(outputs)
        out = self.run_lowered(lp, sharded, out_names, backend=backend)
        return {k: self.unshard_words(v, n_words) for k, v in out.items()}


_CLUSTER_CACHE: Dict[Tuple, ChipCluster] = {}


def get_cluster(n_chips: int, n_banks: int = 8,
                max_chips: Optional[int] = None) -> ChipCluster:
    """Memoized `ChipCluster.create` — the backing for one-shot dispatch
    (`engine.execute(..., n_chips=C)`), so repeated calls reuse one mesh
    and its jitted shard_map executables."""
    key = (n_chips, n_banks, max_chips, len(jax.devices()))
    cl = _CLUSTER_CACHE.get(key)
    if cl is None:
        cl = _CLUSTER_CACHE[key] = ChipCluster.create(
            n_chips, n_banks=n_banks, max_chips=max_chips)
    return cl


# ---------------------------------------------------------------------------
# Controller schedule across chips
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ClusterSchedule:
    """Makespan of a bulk op split across chips (each chip: its own
    internal bus + banks, `bankgroup.pipeline_latency_ns`) plus the
    log2-depth inter-chip reduction tree for aggregate readout."""

    n_blocks: int
    n_chips: int
    n_banks: int
    compute_ns: float      # slowest chip's pipelined makespan
    reduce_ns: float       # ceil(log2 C) tree stages
    total_ns: float


def cluster_latency_ns(n_blocks: int, n_chips: int, n_banks: int,
                       program: Program,
                       timing: DramTiming = DDR3_1600,
                       xfer_ns_per_block: Optional[float] = None
                       ) -> ClusterSchedule:
    """Modeled makespan of `n_blocks` row-block ops over C chips x M banks.

    Blocks split round-robin across chips; each chip pipelines its share
    over its own internal bus and banks (transfers serialize *per chip*,
    not globally — the cross-chip seam is the whole scaling argument), and
    an aggregate readout pays one reduction-tree traversal of depth
    ceil(log2 C), one AAP-time per stage.
    """
    per_chip = [len(r) for r in
                bankgroup.partition_blocks(n_blocks, n_chips)]
    compute = max(
        (bankgroup.pipeline_latency_ns(
            blocks, n_banks, program, timing, xfer_ns_per_block).total_ns
         for blocks in per_chip if blocks),
        default=0.0)
    if xfer_ns_per_block is None:
        xfer_ns_per_block = timing.aap_ns
    reduce = math.ceil(math.log2(n_chips)) * xfer_ns_per_block \
        if n_chips > 1 else 0.0
    return ClusterSchedule(
        n_blocks=n_blocks, n_chips=n_chips, n_banks=n_banks,
        compute_ns=compute, reduce_ns=reduce, total_ns=compute + reduce)


def cluster_throughput_gbps(n_blocks: int, n_chips: int, n_banks: int,
                            program: Program,
                            timing: DramTiming = DDR3_1600) -> float:
    """End-to-end GB/s of output for a multi-block op on the cluster."""
    sched = cluster_latency_ns(n_blocks, n_chips, n_banks, program, timing)
    if sched.total_ns == 0.0:
        return 0.0
    return n_blocks * timing.row_bytes / sched.total_ns

"""Named-bitvector catalog with DRAM row placement.

The query service operates over *named* bitvectors ("the Tuesday activity
bitmap of tenant 3", "the gender attribute bitmap"). The catalog is the
binding between those names and (a) the packed uint32 words that hold the
bits and (b) where those bits live in the modeled DRAM — each registered
vector is placed into subarray rows through `core.allocator.DramAllocator`
(paper §6.2.4 OS support), so co-registered vectors of one tenant land in
one subarray and stay all-FPM reachable while capacity lasts.

Catalog names become the D-group row names of compiled query programs, so
they must stay clear of the reserved B/C-group addresses and the compiler's
temp/canonical-input namespaces — `register` validates that.

In distributed mode (`attach_cluster`) the catalog additionally records a
`ChipPlacement` per vector: its words are sharded over the chip mesh of a
`core.cluster.ChipCluster` and the sharded device copy is cached on the
entry. Affinity groups stay chip-local — group members share one shard
layout, so corresponding word-slots co-reside and queries over a group
never move operand bits between chips. An elastic rescale re-attaches a
new cluster and re-places every entry (slot contents are invariant; only
the slot->chip assignment changes).

Every vector of a catalog shares one width, so the words live as rows of
one device-resident ``(capacity, 1, n_words)`` uint32 **arena** (the unit
axis keeps a row contiguous in a TPU's tiled layout): an entry is its
row (`CatalogEntry.slot`), and the arena is the only device copy of the
words. Rows `ZERO_SLOT` and `ONES_SLOT` hold all-zero and all-one
words, so a plan group's whole plane tensor — reserved rows, operands,
zero tail — is one gather by slot (`lowering.Gather`) inside the group's
compiled dispatch. Registration writes one row in place (a donated
update) and the arena doubles when full.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lowering
from repro.core.allocator import DramAllocator, RowHandle
from repro.core.bitplane import BitVector, n_words, pack_bits, tail_mask

# Reserved row-name patterns: B/C-group addresses, designated/DCC rows, the
# compiler's temp rows, and the planner's canonical input/output names.
_RESERVED_RE = re.compile(
    r"^(B\d+|C[01]|T[0-3]|DCC[01]|TMP\d*|IN\d+|OUT)$")
_NAME_RE = re.compile(r"^[A-Za-z_][\w./:-]*$")

#: arena rows every catalog holds: all-zero and all-one words
ZERO_SLOT, ONES_SLOT = 0, 1
_FIRST_CAPACITY = 32


@functools.partial(jax.jit, donate_argnums=0)
def _write_row(arena: jax.Array, slot: jax.Array,
               words: jax.Array) -> jax.Array:
    """``arena`` with row ``slot`` set to ``words``, updated in place."""
    return jax.lax.dynamic_update_slice_in_dim(arena, words[None, None],
                                               slot, 0)


@jax.jit
def _read_row(arena: jax.Array, slot: jax.Array) -> jax.Array:
    return jax.lax.dynamic_index_in_dim(arena, slot, 0, keepdims=False)[0]


class CatalogError(KeyError):
    pass


def plane_name(column: str, j: int) -> str:
    """Catalog row name of bit-plane j of a registered integer column.

    The one naming convention shared by the service (`register_column`),
    the planner (arithmetic query expansion), and range-scan lowering.
    """
    return f"{column}.b{j}"


@dataclasses.dataclass(frozen=True)
class ChipPlacement:
    """Where one bitvector's word-shards live on the chip mesh.

    In distributed mode every vector is word-partitioned over
    ``n_chips * local_banks`` slots (`core.cluster.ChipCluster`); slot s
    lives on chip ``s // local_banks``. Vectors of one affinity `group`
    share this layout, so slot s of *every* group member is resident on
    the same chip — queries over a group combine operands chip-locally
    and nothing but reduction scalars crosses the chip boundary.
    """

    n_chips: int
    local_banks: int          # slot rows resident per chip
    local_words: int          # packed words per slot (after padding)
    group: Optional[str] = None

    @property
    def slots(self) -> int:
        return self.n_chips * self.local_banks

    def chip_of_slot(self, slot: int) -> int:
        return slot // self.local_banks


@dataclasses.dataclass
class CatalogEntry:
    """One registered bitvector: its arena row + modeled DRAM placement."""

    name: str
    slot: int                 # row of the catalog's arena
    n_bits: int
    handle: RowHandle         # (bank, subarray, row) placement
    group: Optional[str] = None
    #: distributed mode only: the (chip, bank, word) sharded device copy
    #: and its layout record (None until a cluster is attached)
    shards: Optional[jax.Array] = None
    placement: Optional[ChipPlacement] = None
    catalog: Optional["Catalog"] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def words(self) -> jax.Array:
        """The (n_words,) uint32 LSB-first packed words: the arena row."""
        return _read_row(self.catalog.arena, np.int32(self.slot))

    @words.setter
    def words(self, value) -> None:
        # overwrites the stored row (a corruption at rest, to the parity
        # probe); registration is the way to add data
        self.catalog._write(self.slot, value)

    @property
    def n_row_blocks(self) -> int:
        """How many 8KB DRAM rows the vector spans (>= 1)."""
        return self.handle.n_rows


@dataclasses.dataclass
class Catalog:
    """Registry of named bitvectors, placed via the DRAM allocator.

    All vectors in one catalog share a bit domain (`n_bits`) — queries
    combine arbitrary subsets of them, so mixed widths would be a silent
    correctness bug; the first registration pins the width.
    """

    allocator: DramAllocator = dataclasses.field(default_factory=DramAllocator)

    def __post_init__(self):
        self._entries: Dict[str, CatalogEntry] = {}
        self.n_bits: Optional[int] = None
        # integer columns: name -> bit width; planes live as ordinary
        # entries under plane_name(name, j). The planner reads this map to
        # expand arithmetic query forms (sum/+/-/<) into plane programs.
        self.columns: Dict[str, int] = {}
        # distributed mode: the ChipCluster every entry is placed onto
        # (None = single-process catalog, the pre-cluster behavior)
        self._cluster = None
        self._mask_shards: Optional[jax.Array] = None
        # the words of every entry, one row each (None until the first
        # registration pins the width); rows past _n_slots, and released
        # scratch rows in _free, are unused
        self._arena: Optional[jax.Array] = None
        self._n_slots = 2
        self._free: List[int] = []
        # ECC: running XOR parity plane per affinity group (None key =
        # ungrouped), maintained incrementally at registration time —
        # `verify_parity` recomputes from scratch and cross-checks, the
        # integrity probe of the service's "ecc" reliability mode
        self._parity: Dict[Optional[str], jax.Array] = {}

    # -- registration -------------------------------------------------------

    def register(self, name: str, value, n_bits: Optional[int] = None,
                 group: Optional[str] = None) -> CatalogEntry:
        """Register packed uint32 words (or a BitVector) under `name`.

        `group` is the allocator affinity group: vectors registered in one
        group co-locate in one subarray while rows last (all-FPM staging).
        """
        if not _NAME_RE.match(name) or _RESERVED_RE.match(name):
            raise CatalogError(f"invalid or reserved catalog name {name!r}")
        if name in self._entries:
            raise CatalogError(f"catalog name {name!r} already registered")
        if isinstance(value, BitVector):
            words, n_bits = value.words, value.n_bits
        else:
            words = jnp.asarray(value, jnp.uint32)
            if n_bits is None:
                n_bits = int(words.shape[-1]) * 32
        if words.ndim != 1 or words.shape[0] != n_words(n_bits):
            raise CatalogError(
                f"{name!r}: expected ({n_words(n_bits)},) packed words for "
                f"{n_bits} bits, got shape {tuple(words.shape)}")
        if self.n_bits is None:
            self.n_bits = n_bits
        elif n_bits != self.n_bits:
            raise CatalogError(
                f"{name!r}: domain {n_bits} != catalog domain {self.n_bits}")
        handle = self.allocator.alloc(name, n_bits, group=group)
        slot = self._take_slot()
        self._write(slot, words)
        entry = CatalogEntry(name, slot, n_bits, handle, group=group,
                             catalog=self)
        self._entries[name] = entry
        prev = self._parity.get(group)
        cur = jnp.asarray(words, jnp.uint32)
        self._parity[group] = cur if prev is None else prev ^ cur
        if self._cluster is not None:
            self._place(entry)
        return entry

    def register_bits(self, name: str, bits, group: Optional[str] = None
                      ) -> CatalogEntry:
        """Register from a bool/0-1 bit array (packs it first)."""
        bits = jnp.asarray(bits)
        return self.register(name, pack_bits(bits), bits.shape[-1], group)

    def register_column(self, name: str, planes, n_values: int, n_bits: int,
                        group: Optional[str] = None) -> None:
        """Register an integer column: one entry per vertical bit plane.

        `planes` is the (n_bits, n_words) LSB-first plane stack of a
        `VerticalColumn`; plane j lands under `plane_name(name, j)` and the
        column's width is recorded in `self.columns` so arithmetic queries
        (`sum(name)`, `name + other`, `name < K`) can be expanded.
        """
        if name in self.columns:
            raise CatalogError(f"column {name!r} already registered")
        for j in range(n_bits):
            self.register(plane_name(name, j), planes[j], n_values,
                          group=group)
        self.columns[name] = n_bits

    # -- the arena -----------------------------------------------------------

    @property
    def arena(self) -> jax.Array:
        """The ``(capacity, 1, n_words)`` uint32 device rows of every
        entry; ``arena[slot, 0]`` is one entry's words."""
        assert self._arena is not None, "empty catalog has no arena"
        return self._arena

    def _take_slot(self) -> int:
        if self._free:
            return self._free.pop()
        if self._arena is None:
            words = n_words(self.n_bits)
            self._arena = jnp.zeros((_FIRST_CAPACITY, 1, words), jnp.uint32)
            self._write(ONES_SLOT, np.full(words, 0xFFFFFFFF, np.uint32))
        elif self._n_slots == self._arena.shape[0]:
            # doubling: a copy per doubling, none per registration
            self._arena = jnp.pad(self._arena,
                                  ((0, self._arena.shape[0]), (0, 0), (0, 0)))
        self._n_slots += 1
        return self._n_slots - 1

    def _write(self, slot: int, words) -> None:
        self._arena = _write_row(self._arena, np.int32(slot),
                                 jnp.asarray(words, jnp.uint32))

    def scratch_slot(self, words) -> int:
        """Write a plane that lives for one batch (a shared-subexpression
        plane) into a free row; `release` gives the row back."""
        slot = self._take_slot()
        self._write(slot, words)
        return slot

    def release(self, slots: Iterable[int]) -> None:
        self._free.extend(slots)

    def gather(self, rows: Dict[str, np.ndarray]) -> lowering.Gather:
        """Operand rows ``{name: slots}`` of a dispatch, named by arena row
        (`lowering.execute_lowered` builds the plane as one gather)."""
        return lowering.Gather(self.arena, rows, ZERO_SLOT, ONES_SLOT)

    # -- lookup -------------------------------------------------------------

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, name: str) -> CatalogEntry:
        try:
            return self._entries[name]
        except KeyError:
            raise CatalogError(f"unknown catalog name {name!r}") from None

    def names(self) -> List[str]:
        return list(self._entries)

    def row_state(self, names: Iterable[str]) -> Dict[str, jax.Array]:
        """Engine-ready {row name -> words} for a subset of entries."""
        return {n: self.get(n).words for n in names}

    def mask(self) -> jax.Array:
        """Tail mask zeroing the padding bits of the last packed word."""
        assert self.n_bits is not None, "empty catalog has no domain"
        return jnp.asarray(tail_mask(self.n_bits))

    # -- ECC parity planes ----------------------------------------------------

    def parity_plane(self, group: Optional[str] = None) -> jax.Array:
        """The maintained XOR parity of one affinity group's vectors.

        Word-level XOR over the *unsharded* packed words, so the plane is
        invariant across elastic rescales (only slot->chip assignment
        moves, never the words) — what lets the chaos suite assert catalog
        integrity after a chip-kill recovery.
        """
        if group not in self._parity:
            raise CatalogError(f"no vectors registered in group {group!r}")
        return self._parity[group]

    def verify_parity(self) -> bool:
        """Recompute every group's XOR parity and cross-check the
        maintained planes — False means some registered vector's words
        were corrupted (or parity maintenance has a bug)."""
        fresh: Dict[Optional[str], jax.Array] = {}
        for entry in self._entries.values():
            w = jnp.asarray(entry.words, jnp.uint32)
            prev = fresh.get(entry.group)
            fresh[entry.group] = w if prev is None else prev ^ w
        if set(fresh) != set(self._parity):
            return False
        return all(bool(jnp.array_equal(self._parity[g], fresh[g]))
                   for g in fresh)

    # -- chip placement (distributed mode) ------------------------------------

    def _place(self, entry: CatalogEntry) -> None:
        cluster = self._cluster
        entry.shards = cluster.shard_words(entry.words)
        entry.placement = ChipPlacement(
            n_chips=cluster.n_chips, local_banks=cluster.local_banks,
            local_words=int(entry.shards.shape[-1]), group=entry.group)

    def attach_cluster(self, cluster) -> None:
        """Place every registered vector onto a `core.cluster.ChipCluster`.

        Called at service start and again after an elastic `rescale` —
        re-placement re-shards every entry onto the new mesh. The slot
        grid (`cluster.slots`) is invariant across rescales of one
        placement lineage, so the bits held by each slot never move
        between slots; only the slot->chip assignment changes.
        """
        self._cluster = cluster
        self._mask_shards = None
        for entry in self._entries.values():
            self._place(entry)

    @property
    def cluster(self):
        return self._cluster

    def shards(self, name: str) -> jax.Array:
        """The (n_chips, local_banks, local_words) sharded copy of a row."""
        entry = self.get(name)
        if entry.shards is None:
            if self._cluster is None:
                raise CatalogError(
                    f"{name!r} has no chip placement: no cluster attached")
            self._place(entry)
        return entry.shards

    def placement(self, name: str) -> Optional[ChipPlacement]:
        return self.get(name).placement

    def mask_shards(self) -> jax.Array:
        """`mask()` pushed through the cluster's word-shard layout."""
        assert self._cluster is not None, "no cluster attached"
        if self._mask_shards is None:
            self._mask_shards = self._cluster.shard_words(self.mask())
        return self._mask_shards

    # -- placement queries ----------------------------------------------------

    def psm_copies(self, srcs: Iterable[str], dst_group_rep: str) -> int:
        """Operand movements needing PSM for an op over `srcs` (§6.2.2)."""
        return self.allocator.psm_copies_for_op(list(srcs), dst_group_rep)

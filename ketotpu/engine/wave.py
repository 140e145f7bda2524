"""One check wave: the record every launcher fills, and the decisions
every launcher shares.

Three launchers run a wave — the one-chip cascade and the fused wave
(engine/tpu.py), the mesh's cascade (parallel/meshengine.py) — and what
they must agree on is written here once, as plain functions over numpy
arrays: how the verdict words decode, which rows are retried and what a
retry's answer replaces, who answers a row (Leopard, the cache, the
oracle, the device), and how a wave, its general tier and a retry are
padded.  No engine and no jax: tests/test_wave.py runs it in milliseconds.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import numpy as np

from ketotpu.engine.optable import R_ERR, R_IS


@dataclasses.dataclass(slots=True)
class Wave:
    """What ``_dispatch`` hands to ``_collect``: ``n`` rows padded to
    ``qpad``.  A wave is a fused wave exactly when ``meta`` is set."""

    n: int
    qpad: int
    enc: Tuple[np.ndarray, ...]  # id columns: ns, obj, rel, subj, depth
    #: rows for the oracle and its typed error; on the mesh also rows
    #: sent to a peer host or owned by a down shard
    err: np.ndarray
    general: np.ndarray  # rows of the AND/NOT tier
    cursor: int  # freshness stamp of the view, for cache entries
    #: what the wave was encoded against, and a retry runs against: the
    #: device arrays on one chip, the sharded stacks on the mesh
    arrays: Any
    leo_res: Optional[Tuple[Optional[np.ndarray], np.ndarray]] = None
    cache_res: Optional[Tuple[np.ndarray, np.ndarray]] = None
    # (allowed, answered) and (cached, verdicts); None: off, or no hit.
    #: why the closure index may not answer each row (closure.WHY_*);
    #: None while the index is off
    leo_why: Optional[np.ndarray] = None
    # The launcher's uncollected device results: the cascade's fast tier
    # and its occupancy, its general rows and their (codes, occ, rows,
    # fast_b); or a fused wave's one array, which ``meta`` describes
    fast: Any = None
    occ: Any = None
    gi: Optional[np.ndarray] = None
    gen: Optional[tuple] = None
    fused: Any = None
    meta: Optional[dict] = None
    #: general rows of the widest wave of this wave's ticket
    #: (``Cut.like``): the general tier pads as if it held them
    gen_like: int = 0
    # mesh only: each row's serving shard; the exchanges with peer hosts
    assign: Optional[np.ndarray] = None
    peers: Optional[dict] = None


@dataclasses.dataclass(slots=True)
class Ticket:
    """What ``submit`` hands to ``collect``: a batch cut into chunks
    (:func:`cut`), each ``(rows, queries)``: the batch rows a wave holds
    and those queries; each with its launched, uncollected :class:`Wave`;
    or the failure that stopped the launch, for ``collect`` to answer for."""

    queries: Any  # the whole batch: a tuple list or a ColumnBlock
    rest_depth: int
    t0: float  # perf_counter at submit, for the ``device_compute`` stage
    compiles_before: int  # compilewatch total at submit (warm heuristic)
    chunks: List[Tuple[np.ndarray, Any]] = dataclasses.field(
        default_factory=list)
    waves: List[Optional[Wave]] = dataclasses.field(default_factory=list)
    failure: Optional[BaseException] = None


# -- padding ------------------------------------------------------------------


def _bucket(n: int, floor: int = 256) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def _bucket15(n: int, floor: int = 64) -> int:
    """Smallest of {2^k, 1.5*2^k} >= n: pow2 rounding wastes up to ~50%
    of every buffer (and per-level device cost scales with buffer size);
    the half-octave step bounds waste at ~33% while adding at most one
    extra compile variant per octave."""
    b = floor
    while b < n:
        if b * 3 // 2 >= n:
            return b * 3 // 2
        b *= 2
    return b


def wave_rows(n: int, frontier: int) -> int:
    """Rows a wave of ``n`` is padded to: pow2 for compile-cache reuse,
    never beyond the frontier cap.  That ``n`` rows fit a wave is not
    decided here: a row needs several frontier slots by the deepest BFS
    level, so ``submit`` cuts a batch by :func:`wave_cap` first."""
    return min(_bucket(n), frontier)


def general_lanes(n: int, cap: int, like: int = 0) -> int:
    """Root lanes of the general tier for ``n`` AND/NOT rows: every buffer
    of the algebra program scales with them, so they pad by half octaves
    (333 rows run in 384 lanes, not the wave's 1024), one program a
    bucket, up to what the launcher holds (``cap``: the wave's rows when
    fused, ``max_batch`` for a launch of its own).  ``like``: pad as if
    the wave held that many, the most a wave of its ticket holds, so a
    ticket's waves run one program.  No rows, no tier."""
    return min(_bucket15(max(n, like), 256), cap) if n else 0


def retry_rows(k: int, cap: int) -> int:
    """Rows the fast tier's retry of ``k`` overflowed rows is padded to."""
    return min(_bucket(k, 256), cap)


# -- cutting a batch into waves -----------------------------------------------


def wave_cap(schedule: Callable[[int, int, int], tuple], frontier: int,
             arena: int) -> int:
    """Most rows of a wave that the frontier holds: the widest padded wave
    (a power of two, :func:`wave_rows`) whose worst-case level schedule
    ``schedule(rows, frontier, arena)`` (``fastpath.level_schedule``)
    neither cap clips at any level.  A wider wave still compiles, but its
    rows share the deeper levels' slots, overflow, and are answered on
    the host.  Never under the narrowest wave there is."""
    free = 1 << 62
    q = _bucket(1)
    while schedule(2 * q, frontier, arena) == schedule(2 * q, free, free):
        q *= 2
    return q


class Cut(NamedTuple):
    """A batch as the waves of one ticket."""

    #: the batch rows of each wave, ascending
    rows: List[np.ndarray]
    #: (rows, general rows) of the widest wave: every wave of the ticket
    #: pads as if it held them (``wave_rows``, ``general_lanes``), so the
    #: ticket runs one program; (0, 0): pad by the wave's own
    like: Tuple[int, int]


def _dealt(count: int, k: int) -> np.ndarray:
    """``count`` things over ``k`` waves, evenly, the first ones one more."""
    return count // k + (np.arange(k) < count % k)


def cut(n: int, general: Optional[np.ndarray], cap: int) -> Cut:
    """Cut a batch of ``n`` rows into waves of at most ``cap``
    (:func:`wave_cap`).  Up to ``cap`` rows are one wave, untouched.  A
    larger batch becomes the fewest waves that hold it, of equal size
    (one row apart), with the AND/NOT rows (``general``, a mask) dealt
    evenly too: a wave's program is chosen by its padded rows and its
    general rows' bucket, and a blind cut leaves a short last wave and a
    general count that wanders across a bucket's edge, each a program
    nobody compiled.  Every row is in exactly one wave and keeps its
    place among that wave's rows, so ``allowed[rows] = verdicts`` restores
    the request's order."""
    if n <= cap:
        return Cut([np.arange(n)] if n else [], (0, 0))
    k = -(-n // cap)
    n_general = int(general.sum())
    of = np.empty(n, np.int64)
    gens = _dealt(n_general, k)
    of[general] = np.repeat(np.arange(k), gens)
    of[~general] = np.repeat(np.arange(k), _dealt(n, k) - gens)
    return Cut([np.flatnonzero(of == w) for w in range(k)],
               (-(-n // k), -(-n_general // k)))


# -- decoding -----------------------------------------------------------------


class GeneralBits(NamedTuple):
    """The general tier's verdict word (engine/algebra.py)."""

    code: np.ndarray   # bits 0-1: R_* verdict
    over: np.ndarray   # bit 2: a capacity was exhausted
    #: bit 3: the skeleton touched overlay-stale state (a changed edge
    #: row) — under AND/NOT even an IS verdict can be wrong (a missed
    #: child IS inverts through NOT), so the oracle answers
    dirty: np.ndarray


class FastBits(NamedTuple):
    """The fast tier's verdict word (engine/fastpath.py)."""

    found: np.ndarray  # bit 0: monotone and overlay-exact
    over: np.ndarray   # bit 1
    dirty: np.ndarray  # bit 2: touched a CSR row with pending writes


class FusedBits(NamedTuple):
    """A fused wave's ten bits a row (the table in engine/fused.py)."""

    general: GeneralBits     # bits 0-3, post-retry
    found: np.ndarray        # 4: monotone across retry lanes
    fast_fb: np.ndarray      # 5: dirty-unfound, or still over after retries
    leo_ans: np.ndarray      # 6
    leo_allow: np.ndarray    # 7
    retried: np.ndarray      # 8: fast row entered a retry lane
    gen_retried: np.ndarray  # 9


def _bit(words: np.ndarray, k: int) -> np.ndarray:
    return ((words >> k) & 1).astype(bool)


def decode_general(words: np.ndarray) -> GeneralBits:
    return GeneralBits(
        (words & 3).astype(np.int8), _bit(words, 2), _bit(words, 3))


def decode_fast(words: np.ndarray) -> FastBits:
    return FastBits(_bit(words, 0), _bit(words, 1), _bit(words, 2))


def decode_fused(words: np.ndarray) -> FusedBits:
    return FusedBits(
        decode_general(words), *(_bit(words, k) for k in range(4, 10)))


# -- retry, verdict, fallback ---------------------------------------------------


def general_retry_rows(g: GeneralBits) -> np.ndarray:
    """Overflowed, and neither dirty (a retry would read the same stale
    base) nor in error."""
    return g.over & ~g.dirty & (g.code != R_ERR)


def fast_retry_rows(f: FastBits) -> np.ndarray:
    """found is monotone: an overflow only voids not-yet-found rows;
    dirty rows would see the same stale base again."""
    return f.over & ~f.found & ~f.dirty


def take_retry(bits, rows: np.ndarray, retried) -> None:
    """A retried row's answer is the retry's, whole: its bits replace the
    first pass's at ``rows`` (in place) and are read by the rules below
    like any row's — a retry that still overflows, turns dirty or errs
    falls back."""
    for mine, theirs in zip(bits, retried):
        mine[rows] = theirs


def general_allowed(g: GeneralBits) -> np.ndarray:
    return g.code == R_IS


def general_fallback(g: GeneralBits) -> np.ndarray:
    return g.over | g.dirty | (g.code == R_ERR)


def fast_fallback(f: FastBits) -> np.ndarray:
    """A found verdict stands even when the exploration brushed a dirty
    row or overflowed; anything else that did either is the oracle's."""
    return (f.over | f.dirty) & ~f.found


def fused_overflowed(bits: FusedBits) -> Tuple[np.ndarray, np.ndarray]:
    """(fast rows, general rows) of a fused wave that a capacity left
    without a verdict on the first pass, as ``fast_retry_rows`` and
    ``general_retry_rows`` say it of a cascade's: the rows that entered a
    retry lane, and without lanes the rows still flagged (where a
    dirty-unfound fast row and a dirty or erring general row are flagged
    as well: the program returns the first pass folded)."""
    return (bits.retried | bits.fast_fb,
            bits.gen_retried | bits.general.over)


# -- who answers a row --------------------------------------------------------


def _claims(n: int, leo_res, cache_res):
    """(leopard, cache) row masks.  A cache hit on a Leopard-answered row
    does not claim it: the cascade never asks the cache for those, and a
    fused wave, which learns Leopard's answers only at collect, must not
    let a hit take the verdict or the attribution."""
    leo = leo_res[1] if leo_res is not None else np.zeros(n, bool)
    if cache_res is None:
        return leo, np.zeros(n, bool)
    return leo, cache_res[0] & ~leo


def merge(err, general, g_is, g_fb, found, fast_fb, leo_res=None,
          cache_res=None):
    """(allowed, fallback) of a wave from its tiers' per-row bits.  A row
    is answered by the first of: Leopard or the cache (``_claims``; they
    were inactive on the device and never fall back), the oracle (``err``
    rows whatever the device said, and rows the device gave up on), the
    device: the general tier for ``general`` rows (``g_is``, ``g_fb``),
    the fast tier for the others (``found``, ``fast_fb``)."""
    fast = ~(err | general)
    allowed = (general & g_is) | (fast & found)
    fallback = err | (general & g_fb) | (fast & fast_fb)
    leo, cached = _claims(len(err), leo_res, cache_res)
    if leo_res is not None:
        allowed[leo] = leo_res[0][leo]
    if cache_res is not None:
        allowed[cached] = cache_res[1][cached]
    fallback &= ~(leo | cached)
    return allowed, fallback


class Tiers(NamedTuple):
    """Every row of a wave in exactly one mask."""

    cache: np.ndarray
    leopard: np.ndarray
    oracle: np.ndarray
    device: np.ndarray


def attribute(err, fallback, leo_res=None, cache_res=None) -> Tiers:
    """The tier that answered each row, by ``merge``'s precedence."""
    leo, cached = _claims(len(err), leo_res, cache_res)
    oracle = (fallback | err) & ~(leo | cached)
    return Tiers(cached, leo, oracle, ~(leo | cached | oracle))

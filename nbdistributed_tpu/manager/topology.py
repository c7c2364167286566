"""Per-rank environment construction: backend + TPU topology assignment.

The reference assigns one CUDA GPU per rank via an explicit id list with
modulo recycling (reference: process_manager.py:107-112) and lets the
worker pin it (reference: worker.py:135-144).  On TPU the analog is chip
*partitioning*: a single host's chips are split among worker processes
with the TPU runtime's process-bounds environment, so each worker's JAX
sees only its own chip(s) and ``jax.distributed`` stitches them into one
world over ICI.

Also owns the CPU-backend env used by tests/CI — the analog of the
reference's CUDA→Gloo fallback (reference: worker.py:146-149): cross-
process gloo collectives give a real multi-process world on any box.
"""

from __future__ import annotations

import os

from ..utils import PLATFORMS

# v5e single-host chip grids by chip count (x, y); z is always 1 on v5e.
_V5E_GRIDS = {1: (1, 1), 2: (1, 2), 4: (2, 2), 8: (2, 4)}


def cpu_worker_env(base: dict | None = None) -> dict:
    """Env for a CPU-backend worker: force the CPU platform and gloo
    cross-process collectives, and drop the coordinator's ``XLA_FLAGS``
    (one CPU device per worker unless the caller adds flags back)."""
    env = dict(base if base is not None else os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = PLATFORMS["cpu"]
    env["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"
    return env


def parse_chips(spec: str) -> list[int]:
    """Parse an explicit chip-id list (``"2,3"``) — the analog of the
    reference's ``--gpu-ids`` parse (reference: magic.py:456-459, with
    its bad-format message at magic.py:485-488)."""
    try:
        chips = [int(x.strip()) for x in spec.split(",")]
    except ValueError:
        raise ValueError(
            "Invalid chip IDs format. Use comma-separated integers "
            "(e.g. '0,1,3')") from None
    if not chips:
        raise ValueError("empty chip ID list")
    if any(c < 0 for c in chips):
        raise ValueError(f"chip IDs must be >= 0, got {chips}")
    return chips


def _carve_geometry(host: int, chips_per_worker: int):
    """``((hx, hy), cx, cy)`` when the ``host`` chip grid exists and
    divides into aligned (cx,cy) subgrid blocks; None otherwise.  The
    single source of truth for "is this carve geometry known" —
    ``_grid_blocks``, ``_process_bounds`` and ``validate_tpu_request``
    all consult it so their notions cannot diverge."""
    hgrid = _V5E_GRIDS.get(host)
    cx, cy = _V5E_GRIDS.get(chips_per_worker, (1, chips_per_worker))
    if not hgrid or hgrid[0] % cx or hgrid[1] % cy:
        return None
    return hgrid, cx, cy


def _grid_blocks(total_chips: int, chips_per_worker: int) -> list[list[int]]:
    """The aligned (cx,cy) physical subgrid blocks of a ``total_chips``
    host grid, in row-major block order — each block is the chip-id set
    one multi-chip worker may own.  Chip ids map to the physical grid
    row-major (id = x*Y + y on an (X, Y) grid), so a worker's block is
    generally NOT a consecutive id run: 2 workers x 4 chips on a (2,4)
    v5e-8 carve 2x2 subgrids {0,1,4,5} / {2,3,6,7}.  Both the default
    ``TPU_VISIBLE_CHIPS`` assignment and the explicit-chips validation
    derive from this one function so the ids can never contradict the
    declared ``TPU_CHIPS_PER_PROCESS_BOUNDS`` carve."""
    geo = _carve_geometry(total_chips, chips_per_worker)
    if geo is None:
        # No aligned carve exists; fall back to consecutive full runs
        # (partial trailing blocks are dropped — never phantom ids
        # past total_chips; the callers validate totals against
        # _V5E_GRIDS separately).
        return [list(range(b, b + chips_per_worker))
                for b in range(0, total_chips - chips_per_worker + 1,
                               chips_per_worker)]
    (hx, hy), cx, cy = geo
    return [[(ax + i) * hy + (ay + j)
             for i in range(cx) for j in range(cy)]
            for ax in range(0, hx, cx) for ay in range(0, hy, cy)]


def _process_bounds(host: int, chips_per_worker: int,
                    taken: list[list[int]]) -> str | None:
    """``TPU_PROCESS_BOUNDS`` for workers owning the ``taken`` blocks
    (sorted id lists) of a ``host``-chip grid, or None when no coherent
    rectangular process grid is derivable — the blocks aren't aligned
    subgrids of a known host grid, or they don't fill a rows × cols
    box (a diagonal pick of 2 blocks would declare 4 process slots).
    The ONE place block-grid geometry turns into bounds, shared by
    ``tpu_worker_env`` and ``validate_tpu_request``."""
    geo = _carve_geometry(host, chips_per_worker)
    if geo is None:
        return None
    (_, hy), _, cy = geo
    key = [sorted(b) for b in _grid_blocks(host, chips_per_worker)]
    if any(t not in key for t in taken):
        return None
    nby = hy // cy                            # blocks per grid row
    idx = [key.index(t) for t in taken]
    bx = {i // nby for i in idx}
    by = {i % nby for i in idx}
    if len(bx) * len(by) != len(taken):
        return None
    return f"{len(bx)},{len(by)},1"


def _chips_for_rank(chips: list[int], rank: int,
                    chips_per_worker: int) -> list[int]:
    """Rank's slice of an explicit chip list.  A short list raises
    here rather than recycling modulo (the reference recycles GPU ids,
    process_manager.py:107-112, because CUDA contexts can share a
    device; TPU runtime processes cannot share a chip, so recycling
    would pin two workers to one chip and both would die inside the
    runtime).  The validated magic path rejects short lists earlier;
    this keeps the invariant for direct callers of
    ``tpu_worker_env``/``worker_env`` too."""
    base = rank * chips_per_worker
    if base + chips_per_worker > len(chips):
        raise ValueError(
            f"chip list {chips} too short for rank {rank} x "
            f"{chips_per_worker} chip(s)/worker: TPU runtime processes "
            f"cannot share a chip, so ids are never recycled")
    if len(set(chips)) != len(chips):
        raise ValueError(
            f"duplicate ids in chip list {chips}: TPU runtime "
            f"processes cannot share a chip")
    return chips[base:base + chips_per_worker]


def tpu_worker_env(rank: int, world_size: int, *,
                   chips_per_worker: int = 1,
                   chips: list[int] | None = None,
                   host_chips: int | None = None,
                   tpu_ports: list[int],
                   base: dict | None = None) -> dict:
    """Env for a TPU worker owning ``chips_per_worker`` chips of a
    single-host slice (v5e-8 style).

    Uses the TPU runtime's standard multi-process-per-host contract:
    ``TPU_PROCESS_BOUNDS`` / ``TPU_CHIPS_PER_PROCESS_BOUNDS`` carve the
    chip grid, ``TPU_VISIBLE_CHIPS`` pins this worker's chips, and
    ``TPU_PROCESS_ADDRESSES`` lists every worker's TPU-runtime port
    (``tpu_ports``, one per rank — the spawner picks free ones so two
    fleets in a row, or one leaked worker, cannot collide on a fixed
    port).  The platform is pinned here too: a coordinator's own
    ``JAX_PLATFORMS`` / ``XLA_FLAGS`` (a kernel held to the CPU, a test
    run with virtual devices) describe the coordinator, not a worker
    that was asked for a chip.
    ``chips`` pins an explicit chip set — the analog of the
    reference's ``--gpu-ids`` assignment (reference:
    process_manager.py:107-112).  Single-chip workers may pin any
    distinct ids (non-contiguous is fine, e.g. ``2,3`` on a shared
    host); multi-chip workers must each own an aligned physical
    subgrid block (see ``_grid_blocks`` — enforced pre-spawn by
    ``validate_tpu_request``).  Default is the row-major grid carve.
    ``host_chips`` is the host's probed chip count: subgrid geometry
    must be carved from the HOST grid (a 4-chip job on a v5e-8 lives
    on the (2,4) grid, where a 2x2 block is {0,1,4,5}, not {0,1,2,3}).
    Multi-host pods need per-host launch instead (SURVEY §5.8 notes
    the reference has the same single-node assumption at
    worker.py:129).
    """
    env = dict(base if base is not None else os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = PLATFORMS["tpu"]
    total_chips = world_size * chips_per_worker
    if chips_per_worker == 1:
        grid = _V5E_GRIDS.get(total_chips)
        if grid is None:
            raise ValueError(
                f"unsupported single-host chip count {total_chips}; "
                f"supported: {sorted(_V5E_GRIDS)}")
        px, py = grid
        env["TPU_PROCESS_BOUNDS"] = f"{px},{py},1"
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
        env["TPU_VISIBLE_CHIPS"] = (
            str(_chips_for_rank(chips, rank, 1)[0])
            if chips else str(rank))
    else:
        # One worker spanning several chips (e.g. 2 workers x 4 chips).
        # Geometry is carved from the HOST grid when known (else from
        # the requested total): default chips are the first
        # ``world_size`` blocks of the row-major carve, and
        # TPU_PROCESS_BOUNDS is the rectangle those blocks span in
        # block coordinates — the same _grid_blocks geometry
        # validate_tpu_request checks explicit lists against, so the
        # ids and the declared bounds derive from one carve.
        host = host_chips if host_chips in _V5E_GRIDS else total_chips
        cx, cy = _V5E_GRIDS.get(chips_per_worker, (1, chips_per_worker))
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = f"{cx},{cy},1"
        blocks = _grid_blocks(host, chips_per_worker)
        if chips:
            mine = _chips_for_rank(chips, rank, chips_per_worker)
        else:
            if world_size > len(blocks):
                raise ValueError(
                    f"{world_size} worker(s) × {chips_per_worker} "
                    f"chip(s)/worker exceed the host's {len(blocks)} "
                    f"subgrid block(s) of {chips_per_worker} chips")
            mine = blocks[rank]
        env["TPU_VISIBLE_CHIPS"] = ",".join(str(c) for c in mine)
        taken = ([sorted(chips[r * chips_per_worker:
                               (r + 1) * chips_per_worker])
                  for r in range(world_size)] if chips
                 else [sorted(b) for b in blocks[:world_size]])
        # validate_tpu_request rejects non-rectangular picks pre-spawn;
        # a direct caller bypassing it (or an unknown host geometry)
        # gets the linear fallback carve instead of contradictory vars.
        env["TPU_PROCESS_BOUNDS"] = (
            _process_bounds(host, chips_per_worker, taken)
            or f"1,{world_size},1")
    if len(tpu_ports or ()) != world_size:
        raise ValueError(f"need one TPU-runtime port per worker, got "
                         f"{tpu_ports} for {world_size}")
    env["TPU_PROCESS_ADDRESSES"] = ",".join(
        f"localhost:{p}" for p in tpu_ports)
    env["TPU_PROCESS_PORT"] = str(tpu_ports[rank])
    env["CLOUD_TPU_TASK_ID"] = str(rank)
    # Several processes load libtpu on this host, one per chip; without
    # this the runtime's single-loader lock refuses every process after
    # the first (JAX's own multi-process TPU launcher sets the same).
    env["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
    return env


def worker_env(rank: int, world_size: int, backend: str, *,
               chips_per_worker: int = 1, chips: list[int] | None = None,
               host_chips: int | None = None,
               tpu_ports: list[int] | None = None,
               base: dict | None = None) -> dict:
    if backend == "cpu":
        return cpu_worker_env(base)
    if backend == "tpu":
        return tpu_worker_env(rank, world_size,
                              chips_per_worker=chips_per_worker,
                              chips=chips, host_chips=host_chips,
                              tpu_ports=tpu_ports, base=base)
    raise ValueError(f"unknown backend {backend!r}")


# Device nodes a TPU host exposes, one per chip: ``/dev/accel<N>`` with
# the accel driver, ``/dev/vfio/<group>`` with vfio-pci.  The vfio group
# numbers are whatever IOMMU groups the chips landed in (a one-chip v5e
# machine shows ``/dev/vfio/3``), so both probes glob — never test for
# node 0.  ``/dev/vfio/vfio`` is the container node, not a chip.
_CHIP_NODE_GLOBS = ("/dev/accel[0-9]*", "/dev/vfio/[0-9]*")


def available_tpu_chips() -> int | None:
    """Count of this host's TPU chips from its device nodes, without
    initializing JAX (device probes belong to the workers).  Returns
    None when no chip node is visible.

    The reference validates its GPU-id list against
    ``torch.cuda.device_count()`` before spawning (reference:
    magic.py:454-488); this is the TPU analog.
    """
    import glob

    for pattern in _CHIP_NODE_GLOBS:
        nodes = glob.glob(pattern)
        if nodes:
            return len(nodes)
    return None


def validate_tpu_request(world_size: int, chips_per_worker: int,
                         chips: list[int] | None = None) -> int | None:
    """Fail fast (before any spawn) when the requested topology cannot
    fit this host's chips — N workers dying inside the TPU runtime is a
    much worse error message.  Returns the probed host chip count (or
    None when unknowable) so the caller can feed the SAME geometry
    into ``tpu_worker_env(host_chips=...)`` without a second probe.

    With an explicit ``chips`` list, mirrors the reference's pre-spawn
    GPU-id validation (reference: magic.py:454-488): every id must
    exist on this host, and the list must cover ``-n`` workers.  Two
    departures, both because TPU runtime processes cannot share a chip
    the way CUDA contexts share a GPU: short lists are rejected here
    (the reference's API layer would recycle ids modulo, mapping two
    processes onto one device) and so are duplicate ids.
    """
    need = world_size * chips_per_worker
    have = available_tpu_chips()
    if chips is not None:
        if len(chips) < need:
            raise ValueError(
                f"Not enough chip IDs specified. Need {need} "
                f"({world_size} worker(s) × {chips_per_worker} "
                f"chip(s)), got {len(chips)}. Either specify more "
                f"chip IDs or reduce -n.")
        used = chips[:need]
        dups = sorted({c for c in used if used.count(c) > 1})
        if dups:
            raise ValueError(
                f"duplicate chip IDs {dups}: TPU runtime processes "
                f"cannot share a chip")
        if have is not None:
            invalid = sorted({c for c in used if c >= have})
            if invalid:
                raise ValueError(
                    f"Invalid chip IDs: {invalid}. Available chips: "
                    f"{list(range(have))}")
        if chips_per_worker > 1 and _carve_geometry(have, chips_per_worker):
            # TPU_CHIPS_PER_PROCESS_BOUNDS declares a contiguous
            # (cx,cy) physical subgrid per worker; a TPU_VISIBLE_CHIPS
            # set that is not such a subgrid (e.g. '0,2,4,6')
            # contradicts that carve and the runtime may reject or
            # mis-map it.  Each worker's slice must be one of the
            # aligned subgrid blocks of the host grid, and the blocks
            # together must fill a rectangle of the block grid (the
            # process grid is rectangular).  Blocks are not always
            # consecutive ids: 4 chips/worker on a (2,4) v5e-8 is
            # {0,1,4,5} / {2,3,6,7}.  (Block reuse needs no check:
            # blocks partition the id space, so reuse implies
            # duplicate ids, rejected above.)  Unknown or non-v5e host
            # geometry skips these checks entirely — trust the user,
            # as with the availability check below; never re-anchor to
            # the request size (a (1,2) block at ids [2,3] is legal on
            # a real v5e-8 even though a 2-chip grid wouldn't hold it).
            blocks = [sorted(b)
                      for b in _grid_blocks(have, chips_per_worker)]
            taken = []
            for r in range(world_size):
                sl = used[r * chips_per_worker:(r + 1) * chips_per_worker]
                if sorted(sl) not in blocks:
                    raise ValueError(
                        f"chip IDs {sl} for worker {r} do not form a "
                        f"contiguous physical subgrid of "
                        f"{chips_per_worker} chips: multi-chip workers "
                        f"carve aligned subgrids, one of {blocks}")
                taken.append(sorted(sl))
            if _process_bounds(have, chips_per_worker, taken) is None:
                raise ValueError(
                    f"chip blocks {taken} do not fill a rectangle of "
                    f"the host's block grid: the TPU process grid is "
                    f"rectangular, so the workers' blocks must span a "
                    f"full rows × cols box (a diagonal pick like "
                    f"[0,1]+[6,7] declares 4 process slots for 2 "
                    f"workers)")
    if have is not None and need > have:
        # Suggest the largest world size that both fits the host AND
        # lands on a supported grid — advice the next attempt can
        # actually follow.
        fits = [w for w in range(have // chips_per_worker, 0, -1)
                if w * chips_per_worker in _V5E_GRIDS]
        hint = (f"Use -n {fits[0]}" if fits
                else "No supported topology fits; use --backend cpu")
        raise ValueError(
            f"requested {world_size} worker(s) × {chips_per_worker} "
            f"chip(s) = {need} TPU chips, but this host has {have}. "
            f"{hint} (or --backend cpu for a CPU world).")
    if need not in _V5E_GRIDS:
        raise ValueError(
            f"unsupported single-host chip count {need}; supported: "
            f"{sorted(_V5E_GRIDS)}")
    return have


def detect_backend() -> str:
    """'tpu' if this host has TPU chips, else 'cpu' — what ``--backend
    auto`` resolves to in a notebook.  Checked without initializing
    JAX in the coordinator (device probes are the workers' job); tools
    that measure pass ``tpu`` explicitly and never come through here."""
    return "tpu" if available_tpu_chips() else "cpu"

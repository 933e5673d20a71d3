"""Execution context: ``CylonEnv`` + communicator configs.

TPU-native replacement for the reference's context + communicator bootstrap
(reference: ctx/cylon_context.hpp:30 ``CylonContext::Init/InitDistributed``,
net/comm_config.hpp, net/mpi/mpi_communicator.hpp:26 ``MPIConfig``).

Design shift (SURVEY.md §7): the reference is multi-process SPMD bootstrapped
by MPI/UCX/Gloo; the TPU build is **single-controller SPMD** — one Python
process drives an N-device ``jax.sharding.Mesh`` and the mesh *is* the world.
``rank`` becomes a device index, the hand-rolled channel/AllToAll engine
(net/ops/all_to_all.hpp:78) becomes XLA collectives inside ``shard_map``, and
MPI_Init becomes ``jax.distributed.initialize`` (multi-host, optional).

Config classes keep the reference's naming so user code reads the same:
``CylonEnv(config=TPUConfig())`` ~ ``CylonEnv(config=MPIConfig())``.
"""

from __future__ import annotations

import itertools
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..status import InvalidError

ROW_AXIS = "cyl_rows"  # the mesh axis tables are row-sharded over


def _distributed_initialized() -> bool:
    return jax.distributed.is_initialized()


class CommConfig:
    """Base communicator config (reference: net/comm_config.hpp)."""

    comm_type = "local"

    def resolve_devices(self) -> list[Any]:
        raise NotImplementedError


class LocalConfig(CommConfig):
    """Serial context: world size 1, no collectives (reference Init())."""

    comm_type = "local"

    def resolve_devices(self):
        return [jax.devices()[0]]


class TPUConfig(CommConfig):
    """Bind ranks to accelerator chips via a 1-D device mesh.

    ``world_size=None`` uses every visible device.  ``devices`` may pin an
    explicit device list.  ``distributed=True`` calls
    ``jax.distributed.initialize`` first (multi-host DCN bootstrap — the
    moral slot of the reference's Redis/MPI OOB, §2 C15).
    """

    comm_type = "tpu"

    def __init__(self, world_size: int | None = None, devices: Sequence[Any] | None = None,
                 distributed: bool = False, coordinator_address: str | None = None,
                 process_id: int | None = None, num_processes: int | None = None):
        self.world_size = world_size
        self.devices = list(devices) if devices is not None else None
        self.distributed = distributed
        self.coordinator_address = coordinator_address
        self.process_id = process_id
        self.num_processes = num_processes

    def resolve_devices(self):
        if self.distributed and not _distributed_initialized():
            jax.distributed.initialize(
                coordinator_address=self.coordinator_address,
                num_processes=self.num_processes,
                process_id=self.process_id,
            )
        devs = self.devices if self.devices is not None else list(jax.devices())
        if self.world_size is not None:
            if self.world_size > len(devs):
                raise InvalidError(
                    f"world_size {self.world_size} > visible devices {len(devs)}")
            devs = devs[: self.world_size]
        # slice-major rank numbering (cylon_tpu/topo, docs/topology.md):
        # on a multi-slice fleet the mesh axis orders devices by
        # (slice_index, position) so rank // ranks_per_slice == slice —
        # the layout premise of the two-hop exchange's order-preservation
        # proof and of repart's global index math.  Single-slice fleets
        # and CPU grids come back untouched.
        from ..topo.model import slice_major_order
        return slice_major_order(devs)


class CPUMeshConfig(TPUConfig):
    """Host-CPU simulated grid (tests): the analog of the reference's
    ``mpirun --oversubscribe`` localhost testing (SURVEY.md §4.3).  Requires
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""

    comm_type = "cpu-mesh"

    def resolve_devices(self):
        if self.devices is not None:
            devs = list(self.devices)
        else:
            # jax.devices("cpu") initializes ONLY the cpu client — never call
            # plain jax.devices() here, it would initialize the default
            # (accelerator) backend just to filter it out again.
            devs = list(jax.devices("cpu"))
        if self.world_size is not None:
            if self.world_size > len(devs):
                raise InvalidError(
                    f"world_size {self.world_size} > visible CPU devices "
                    f"{len(devs)} — set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={self.world_size}")
            devs = devs[: self.world_size]
        return devs


def device_config(world_size: int | None = None) -> TPUConfig:
    """The config of a driver script that takes no device option: the
    CPU mesh ONLY where the process was pinned to the CPU on purpose
    (``JAX_PLATFORMS=cpu`` / ``jax_platforms``, as the test rig does);
    otherwise the TPU, and no TPU is an error — never a silent CPU run
    under a device metric's name."""
    from .. import config
    if config._cpu_only():
        return CPUMeshConfig(world_size=world_size)
    d = jax.devices()[0]
    if d.platform != "tpu":
        raise InvalidError(
            f"no TPU found — jax reports platform {d.platform!r} "
            f"({d.device_kind}); set JAX_PLATFORMS=cpu to run on the CPU "
            "mesh on purpose")
    return TPUConfig()


_seq = itertools.count()


class CylonEnv:
    """The world handle (reference: python/pycylon frame.py:90 ``CylonEnv``,
    C++ ``CylonContext``).  Holds the device mesh, rank/world bookkeeping, a
    string config map, and the per-collective sequence counter."""

    #: monotonically assigned per-env id — prediction caches key on this
    #: instead of id(mesh) (CPython reuses ids after GC, which would let a
    #: new env inherit a dead env's capacity predictions)
    _next_serial = 0

    def __init__(self, config: CommConfig | None = None, verbose: bool = False):
        self.config = config or LocalConfig()
        self.verbose = verbose
        devs = self.config.resolve_devices()
        self._devices = devs
        self._mesh = Mesh(np.asarray(devs, dtype=object), (ROW_AXIS,))
        # spot/preemptible semantics: arm the SIGTERM grace drain when
        # CYLON_TPU_PREEMPT_GRACE_S declares a budget (exec/preempt —
        # one env read and no handler otherwise)
        from ..exec.preempt import install as _install_preempt
        _install_preempt()
        self._conf: dict[str, str] = {}
        self._finalized = False
        self.serial = CylonEnv._next_serial
        CylonEnv._next_serial += 1

    # -- reference CylonContext surface ------------------------------------
    @property
    def world_size(self) -> int:
        return len(self._devices)

    @property
    def rank(self) -> int:
        # Single-controller: the controller addresses all ranks; expose the
        # process index for multi-host parity with GetRank().
        return jax.process_index()

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    @property
    def devices(self):
        return list(self._devices)

    @property
    def is_distributed(self) -> bool:
        return self.world_size > 1

    @property
    def topology(self):
        """The mesh's tier model (cylon_tpu/topo — slice count, ranks
        per slice, discovery source; docs/topology.md).  Single-slice
        on fleets without slice attributes and without a
        ``CYLON_TPU_SLICES`` declaration."""
        from ..topo import model as _topo_model
        return _topo_model.topology(self._mesh)

    def sharding(self, spec: P | None = None) -> NamedSharding:
        """NamedSharding over this env's mesh; default = row-sharded."""
        return NamedSharding(self._mesh, P(ROW_AXIS) if spec is None else spec)

    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self._mesh, P())

    def get_next_sequence(self) -> int:
        """Monotone op id (reference cylon_context.hpp:135 edge-id allocator;
        here only used for tracing tags — XLA orders collectives for us)."""
        return next(_seq)

    def add_config(self, key: str, value: str) -> None:
        self._conf[key] = value

    def get_config(self, key: str, default: str = "") -> str:
        return self._conf.get(key, default)

    # -- collective surface (reference net/communicator.hpp:31-69) ---------
    def allgather(self, table):
        """AllGather(Table): every shard receives every row."""
        from ..parallel.collectives import allgather_table
        return allgather_table(table)

    def gather(self, table, root: int = 0):
        """Gather(Table, root): all rows onto shard ``root``."""
        from ..parallel.collectives import gather_table
        return gather_table(table, root)

    def bcast(self, table, root: int = 0):
        """Bcast(Table): replicate shard ``root``'s rows to every shard."""
        from ..parallel.collectives import bcast_table
        return bcast_table(table, root)

    def allreduce(self, column_or_array, op: str = "sum", valid_counts=None):
        """AllReduce(Column, op): elementwise across shards -> host array.
        Pass the owning table's ``valid_counts`` to mask capacity padding."""
        from ..parallel.collectives import allreduce
        return allreduce(column_or_array, op, valid_counts)

    def barrier(self) -> None:
        """Synchronization barrier (reference Barrier()).

        Multi-process (``jax.distributed``): a REAL cross-process barrier —
        every process blocks until all reach it (the reference's
        MPI_Barrier).  Single-process: drains queued work on every device
        of the env."""
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(
                f"cylon_env_barrier_{next(_seq)}")
            return
        for d in self._devices:
            jax.block_until_ready(jax.device_put(np.zeros((), np.int32), d))

    def finalize(self) -> None:
        self._finalized = True

    def __repr__(self) -> str:  # pragma: no cover
        return (f"CylonEnv(world={self.world_size}, comm={self.config.comm_type}, "
                f"devices={[str(d) for d in self._devices]})")

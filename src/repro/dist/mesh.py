"""Context-managed current-mesh registry.

The model code never takes a mesh argument: layers ask ``repro.dist.sharding``
for the active mesh at trace time and pin activations with
``with_sharding_constraint`` only when one is installed. ``use_mesh`` is the
single entry point — it pushes onto a process-local stack *and* enters jax's
own mesh context so bare-``PartitionSpec`` constraints resolve too.

Importing this module must never touch jax device state (the smoke tests run
on 1 CPU device; only launch/dryrun.py forces 512 virtual devices).
"""
from __future__ import annotations

import contextlib

import numpy as np

import jax
from jax.sharding import AxisType, Mesh

_MESH_STACK: list[Mesh] = []
_DIST_INITIALIZED = False


def init_distributed(coordinator: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None,
                     local_device_ids=None) -> bool:
    """Bring up the multi-host runtime (``jax.distributed.initialize``).

    Call once per process, before the first device query. With no
    ``coordinator`` and ``num_processes`` in (None, 0, 1) this is a
    documented no-op — the single-host default of the launch CLIs — so
    tests and one-box serving never touch the distributed client.
    Idempotent: a second call after a successful init returns True without
    re-initializing. Returns True when a multi-process runtime is up.

    The launch CLIs reach this through ``--coordinator``/``--num-hosts``/
    ``--host-id``; afterwards ``jax.devices()`` spans every host and
    ``host_mesh(..., n_pod=...)`` lays the "pod" axis on host boundaries
    (see ``host_boundary_groups``), which is what lets the MPE packed
    subtables row-shard *across* hosts under
    ``host_packed_table_pspecs``."""
    global _DIST_INITIALIZED
    if coordinator is None and num_processes in (None, 0, 1):
        return _DIST_INITIALIZED
    if _DIST_INITIALIZED:
        return True
    kwargs = {}
    if coordinator is not None:
        kwargs["coordinator_address"] = coordinator
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    jax.distributed.initialize(**kwargs)
    _DIST_INITIALIZED = True
    return True


def host_boundary_groups() -> list[list]:
    """Visible devices grouped by owning process (host), process-major.

    Group ``g`` holds the devices whose ``process_index`` is the g-th
    smallest — the host boundary a leading ("pod", ...) mesh axis must
    align with so the inner ("data", "model") axes stay host-local:
    row-shard groups and a2a peer rings then cross the network only along
    "pod". Single-process returns one group with every device."""
    groups: dict[int, list] = {}
    for dev in jax.devices():
        groups.setdefault(dev.process_index, []).append(dev)
    return [groups[p] for p in sorted(groups)]


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Install ``mesh`` as the current mesh for the dynamic extent.

    Nestable; the innermost mesh wins. Also enters the jax mesh context so
    library code using bare PartitionSpecs under pjit keeps working.
    """
    _MESH_STACK.append(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _MESH_STACK.pop()


def current_mesh() -> Mesh | None:
    """The innermost ``use_mesh`` mesh, else jax's own ambient mesh, else None."""
    if _MESH_STACK:
        return _MESH_STACK[-1]
    try:  # a plain `with mesh:` entered outside repro.dist still counts
        from jax._src.mesh import thread_resources
        env_mesh = thread_resources.env.physical_mesh
        if env_mesh is not None and not env_mesh.empty:
            return env_mesh
    except Exception:  # noqa: BLE001 — internal API; absence means "no mesh"
        pass
    return None


def make_device_mesh(shape: tuple[int, ...], axis_names: tuple[str, ...]) -> Mesh:
    """Mesh over the available devices (prod 16×16 / 2×16×16, tests 1×N CPU).

    Every axis is Auto, as in ``host_mesh``: the model code pins layouts
    with ``with_sharding_constraint``, which only names Auto axes."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(shape))


def parse_mesh_flag(flag: str | None) -> Mesh | None:
    """``--mesh`` CLI flag → a host mesh, or None.

    ``"dp,mp"`` (e.g. ``"2,2"``) builds a ("data", "model") mesh;
    ``"pod,dp,mp"`` (e.g. ``"1,2,2"``) a ("pod", "data", "model") multi-pod
    mesh — the shard wrappers are axis-generic, so everything that runs on
    the two-axis mesh runs on the three-axis one (batch spreads over every
    non-"model" axis). Fails loudly when fewer than the product of the axis
    sizes are visible — virtualize CPU devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``. ``"auto"``
    spreads every visible device on the data axis; None/"" disables.
    """
    if not flag:
        return None
    if flag == "auto":
        return host_mesh()
    try:
        sizes = tuple(int(x) for x in flag.split(","))
        if len(sizes) not in (2, 3):
            raise ValueError(flag)
    except ValueError as e:
        raise SystemExit(
            f"--mesh expects 'dp,mp', 'pod,dp,mp' or 'auto', got {flag!r}"
        ) from e
    n_need = 1
    for s in sizes:
        n_need *= s
    n_dev = len(jax.devices())
    if n_need > n_dev:
        raise SystemExit(
            f"--mesh {flag}: needs {n_need} devices, "
            f"{n_dev} visible (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_need})")
    if len(sizes) == 2:
        return host_mesh(n_data=sizes[0], n_model=sizes[1])
    return host_mesh(n_data=sizes[1], n_model=sizes[2], n_pod=sizes[0])


def host_mesh(n_data: int | None = None, n_model: int = 1,
              n_pod: int | None = None) -> Mesh:
    """("data", "model") mesh over host devices — the test-time mesh — or,
    with ``n_pod``, the multi-pod ("pod", "data", "model") layout.

    Defaults to all visible devices on the data axis. Under
    ``--xla_force_host_platform_device_count=4`` this yields a real 4-way
    mesh; on a stock single-device CPU it is a 1×1 mesh, on which every
    constraint in ``repro.dist.sharding`` is a no-op.
    """
    devs = jax.devices()
    if n_data is None:
        n_data = len(devs) // ((n_pod or 1) * n_model)
    if n_pod is None:
        grid = np.asarray(devs[: n_data * n_model]).reshape(n_data, n_model)
        return Mesh(grid, ("data", "model"))
    if len({d.process_index for d in devs}) > 1:
        # multi-host: order host-major so "pod" boundaries are host
        # boundaries and the inner axes stay host-local
        devs = [d for group in host_boundary_groups() for d in group]
    grid = np.asarray(devs[: n_pod * n_data * n_model]).reshape(
        n_pod, n_data, n_model)
    return Mesh(grid, ("pod", "data", "model"))

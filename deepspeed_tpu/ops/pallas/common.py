"""Shared runtime policy for the hand-written Pallas kernels: ONE home
for backend detection and mesh-axis introspection, so the kernels, the
dispatch layers and the serving engine cannot drift on when the
interpreter runs or how remote copies are addressed."""
import jax


def default_interpret():
    """Interpreter mode whenever the backend is not a real TPU — the
    numerics-pinning vehicle for tier-1/dryrun, never a fast path. The
    serving engine's ``auto`` resolution and both kernel families read
    THIS predicate (docs/pallas_kernels.md)."""
    return jax.default_backend() != "tpu"


def bound_axes():
    """Named mesh axes bound at this trace point (the shard_map scope),
    in mesh order — what a remote copy must address. Returns None when
    the (private) introspection API is unimportable; callers must treat
    None as UNSUPPORTED, never as single-axis — guessing the neighbor
    address on a multi-axis mesh would corrupt results silently."""
    try:
        from jax._src.core import get_axis_env
    except ImportError:      # degrade LOUDLY via the callers' fallback
        return None
    return tuple(n for n in get_axis_env().axis_sizes if n is not None)


def split_axes(mesh, names, dim):
    """PartitionSpec entry that splits a dimension of size ``dim`` over
    those of the axes ``names`` the mesh really spans (size > 1) — or
    None, replicated, when there are none or they do not divide it."""
    axes = tuple(n for n in names if int(mesh.shape.get(n, 1)) > 1)
    size = 1
    for n in axes:
        size *= int(mesh.shape[n])
    if not axes or dim % size:
        return None
    return axes[0] if len(axes) == 1 else axes


def shard_kernel(kernel, mesh, in_specs, out_specs):
    """``kernel`` (a function that calls a Mosaic kernel) made fit for a
    program that spans ``mesh``. GSPMD cannot partition a Mosaic kernel
    ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map"), and the lowering equally refuses a region
    that is manual over only SOME of the mesh's axes — so the kernel
    runs under a shard_map over every axis that is not manual already
    at this trace point (an engine's own shard_map may have bound some).
    The specs name the axes that split an operand (:func:`split_axes`);
    whatever they leave out is replicated. ``mesh`` None or of one
    device, and a trace point already manual over the whole mesh,
    return ``kernel`` as it is."""
    if mesh is None or mesh.size == 1:
        return kernel
    from jax.sharding import PartitionSpec as P
    manual = set(bound_axes() or ())
    free = set(mesh.axis_names) - manual
    if not free:
        return kernel

    def unbound(entry):
        names = entry if isinstance(entry, tuple) else (entry,)
        names = tuple(n for n in names if n not in manual)
        return names[0] if len(names) == 1 else (names or None)

    def strip(specs):
        return jax.tree_util.tree_map(
            lambda spec: P(*(unbound(e) for e in spec)), specs,
            is_leaf=lambda x: isinstance(x, P))

    # nested in a manual region the context mesh carries the axis types
    return jax.shard_map(kernel, mesh=None if manual else mesh,
                         in_specs=strip(in_specs),
                         out_specs=strip(out_specs),
                         axis_names=free, check_vma=False)


def pallas_ring_env_supported():
    """Whether THIS trace environment can run the ring kernels:
    ``(ok, reason)``. Two gates — the axis introspection must work (the
    remote-copy address is derived from it), and off-TPU the jax
    interpreter's remote-copy simulation addresses a single named axis
    only (real hardware takes the full MESH device-id tuple)."""
    axes = bound_axes()
    if axes is None:
        return False, ("cannot introspect the bound mesh axes on this "
                       "jax version — remote-copy addressing would be "
                       "a guess")
    if default_interpret() and len(axes) > 1:
        return False, ("multi-axis mesh (e.g. DP x TP) off-TPU: the "
                       "interpreter's remote-copy simulation addresses "
                       "a single named axis; the kernels run on real "
                       "TPU there")
    return True, None

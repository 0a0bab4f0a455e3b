"""Device-mesh construction for the sharded sweep.

The reference's only parallelism is a numba ``prange`` thread pool over DM
trials (``pulsarutils/dedispersion.py:174-181``).  The TPU-native design
maps that onto a 2-D ``jax.sharding.Mesh``:

* ``"dm"`` axis — embarrassingly-parallel trial sharding (the prange
  equivalent; no communication);
* ``"chan"`` axis — channel sharding of the input filterbank, with a
  ``psum`` over partial dedispersed sums (the "tensor-parallel" analogue,
  collective rides ICI);
* a separate ``"time"`` axis mesh drives the ring-halo streaming path
  (:mod:`.stream`) — the sequence-parallel analogue for 1M+-sample chunks.

Multi-host note: all construction goes through ``jax.devices()``, so under
``jax.distributed`` initialisation the same code lays the mesh over every
host's local devices and the collectives ride ICI/DCN as laid out by XLA.
"""

from __future__ import annotations

import numpy as np


def shard_map_compat(f, *, mesh, in_specs, out_specs, check_vma=True):
    """The ONE call site of ``jax.shard_map`` (every mesh kernel builder
    routes through here; the ``retrace-shard-map`` lint enforces it), so
    the next change of that API is one edit.  Written for the installed
    JAX only: no branch for versions that are not."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(shape=None, axis_names=("dm", "chan"), devices=None):
    """Build a ``Mesh`` over the available devices.

    ``shape=None`` puts every device on the first axis.  ``shape`` entries
    may include ``-1`` (inferred).  Total must divide the device count; the
    mesh uses the first ``prod(shape)`` devices.
    """
    import jax
    from jax.sharding import Mesh

    devices = list(jax.devices()) if devices is None else list(devices)
    ndev = len(devices)
    if shape is None:
        shape = (ndev,) + (1,) * (len(axis_names) - 1)
    shape = list(shape)
    if -1 in shape:
        known = int(np.prod([s for s in shape if s != -1]))
        shape[shape.index(-1)] = ndev // known
    total = int(np.prod(shape))
    if total > ndev:
        raise ValueError(f"mesh shape {tuple(shape)} needs {total} devices, "
                         f"have {ndev}")
    grid = np.array(devices[:total]).reshape(shape)
    return Mesh(grid, tuple(axis_names))


def balanced_2d_mesh(n_devices=None):
    """A (dm, chan) mesh that puts most parallelism on the free ``dm`` axis
    but keeps a non-trivial ``chan`` dimension when enough devices exist
    (so the channel-psum path is actually exercised)."""
    import jax

    ndev = n_devices if n_devices is not None else len(jax.devices())
    chan = 2 if ndev % 2 == 0 and ndev >= 4 else 1
    return make_mesh((ndev // chan, chan), ("dm", "chan"))


def pad_to_multiple(array, axis, multiple, mode="edge"):
    """Pad ``array`` along ``axis`` so its length is a multiple.

    Returns ``(padded, original_length)``.  Used to make trial/channel
    counts divisible by the mesh axis sizes (padded trials are duplicates,
    padded channels are zeros — both exact no-ops for the search result
    after slicing back).
    """
    n = array.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return array, n
    widths = [(0, 0)] * array.ndim
    widths[axis] = (0, pad)
    kwargs = {} if mode != "constant" else {"constant_values": 0}
    return np.pad(array, widths, mode=mode, **kwargs), n


def fetch_global(arr):
    """Global (possibly multi-process-sharded) jax array -> host numpy.

    On a multi-process cluster a globally-sharded array spans devices
    the local process cannot address and plain ``np.asarray`` raises —
    found live by ``tools/multihost_live.py`` (round 5).
    ``process_allgather`` assembles the full value on every host;
    single-process keeps the zero-copy fetch.  Safe on plain
    numpy/host inputs.
    """
    import numpy as np

    import jax

    if isinstance(arr, jax.Array) and jax.process_count() > 1:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(arr,
                                                            tiled=True))
    return np.asarray(arr)

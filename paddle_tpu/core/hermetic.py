"""Child-process environments (reference pattern: the fake-device / CPU
simulation contract in test/legacy_test/test_dist_base.py:957 — a CPU-bound
child must not attach the parent's accelerator runtime).

A TPU chip belongs to ONE process at a time: the first process that
initializes the backend takes every chip it can see, and a second process
that needs one of them fails or hangs.  So every spawn path decides up front
what its child may touch: nothing (:func:`cpu_child_env` — PS shard servers,
``launch --backend cpu`` workers, test subprocesses) or exactly one chip
(:func:`one_chip_env` — launcher and fleet workers on a multi-chip host).
The parent itself must never have initialized the backend.
"""
import glob
import os


def local_chip_count():
    """Local TPU chips, counted from their device nodes (0 on a host with
    none).  For parents that size a job by the chips: asking jax would
    initialize the backend and take the chips away from the children."""
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def cpu_child_env(base=None, **extra):
    """Environment mapping for a child process that must run on XLA:CPU.

    Starts from ``base`` (default: ``os.environ``), forces
    ``JAX_PLATFORMS=cpu`` (which libtpu honours: the chip is never opened),
    then applies ``extra``.
    """
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def one_chip_env(chip, base=None, **extra):
    """Environment mapping for a child process that owns local chip ``chip``
    and no other: libtpu's own visibility variables, so sibling workers on a
    multi-chip host each open a different chip instead of racing for all of
    them.  The child sees one device (``jax.devices()`` has length 1).
    """
    env = dict(os.environ if base is None else base)
    env.update({
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    })
    env.update(extra)
    return env

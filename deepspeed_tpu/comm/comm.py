"""Communication shim — the analog of ``deepspeed/comm/comm.py``.

The reference exposes module-level collectives over a global backend object
(``comm/comm.py:222-520``) wrapping torch.distributed/NCCL. On TPU there are two
communication contexts, and this module serves both under the same verb names:

1. **In-trace** (inside ``jit``/``shard_map``): collectives are ``jax.lax`` ops
   over a named mesh axis and are compiled into the program; these are the hot
   paths and map 1:1 — all_reduce→psum, reduce_scatter→psum_scatter,
   all_gather→all_gather, all_to_all(_single)→all_to_all, send/recv→ppermute.
   Pass ``axis_name`` (str or tuple) instead of the reference's ``group``.

2. **Host-level** (outside jit): process bring-up and occasional scalar syncs.
   ``init_distributed`` mirrors ``comm/comm.py:604`` (env discovery →
   ``jax.distributed.initialize``); ``get_rank``/``get_world_size`` are process
   rank/count; ``barrier`` synchronizes processes.

Every verb is wrapped by ``timed_op`` feeding the comms logger, mirroring
``comm/comm.py:101``.
"""

import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


from deepspeed_tpu.resilience import faults as _faults
from deepspeed_tpu.utils.logging import logger


class ReduceOp:
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"
    PRODUCT = "prod"


_comms_logger = None
_initialized = False


def configure(comms_config=None, enabled=None, prof_all=None, prof_ops=None, verbose=None):
    """Configure comms logging (reference ``comm/comm.py`` configure)."""
    global _comms_logger
    from deepspeed_tpu.utils.comms_logging import CommsLogger
    if _comms_logger is None:
        _comms_logger = CommsLogger()
    _comms_logger.configure(comms_config=comms_config, enabled=enabled,
                            prof_all=prof_all, prof_ops=prof_ops, verbose=verbose)


def get_comms_logger():
    global _comms_logger
    if _comms_logger is None:
        from deepspeed_tpu.utils.comms_logging import CommsLogger
        _comms_logger = CommsLogger()
    return _comms_logger


def _in_trace(x):
    if isinstance(x, (list, tuple)):
        return any(_in_trace(t) for t in x)
    return isinstance(x, jax.core.Tracer)


def _nbytes(x):
    """Message size in bytes; list verbs (all_to_all, coalesced) sum their
    leaves. Works for concrete arrays AND tracers (aval shape/dtype)."""
    if isinstance(x, (list, tuple)):
        return sum(_nbytes(t) for t in x)
    try:
        return int(x.size) * x.dtype.itemsize
    except Exception:
        return 0


def timed_op(fn):
    """Profiling wrapper (reference ``comm/comm.py:101``).

    Host-level calls are timed with ``block_until_ready`` and fed to the
    comms logger when it is enabled. In-trace calls (inside jit/shard_map)
    compile into the program, so their device latency cannot be observed
    here — but the message size and mesh axis are known at trace time, so
    when telemetry is on each traced collective is recorded (tagged
    ``traced=True``, duration = host trace-emission time) giving per-op
    per-axis byte totals even for fully-jitted training loops."""
    import inspect
    try:
        _axis_default = inspect.signature(fn).parameters["axis_name"].default
    except Exception:
        _axis_default = None

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        from deepspeed_tpu import telemetry
        log = _comms_logger
        # quantized collectives pass the true on-the-wire byte count
        # (packed ints + scales); plain collectives omit it
        wire_bytes = kwargs.pop("wire_bytes", None)
        tensor = args[0] if args else kwargs.get("tensor")
        axis = kwargs.get("axis_name", _axis_default)
        tm_on = telemetry.enabled()
        if _in_trace(tensor):
            if not tm_on:
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            result = fn(*args, **kwargs)
            telemetry.record_comm(fn.__name__, _nbytes(tensor),
                                  time.perf_counter() - t0, axis=axis,
                                  traced=True, wire_bytes=wire_bytes)
            return result
        # host-level (non-traced) collective: where real comm faults strike.
        # comm.partition models a whole slice dropping off the DCN fabric —
        # the elastic reshard path (resilience/elastic_reshard.py) catches
        # the InjectedFault and shrinks to the survivors instead of dying
        _faults.maybe_fail("comm.partition", detail=fn.__name__)
        _faults.maybe_fail("comm.collective", detail=fn.__name__)
        if (log is None or not log.enabled) and not tm_on:
            return fn(*args, **kwargs)
        t0 = time.perf_counter()
        result = fn(*args, **kwargs)
        try:
            jax.block_until_ready(result)
        except Exception:
            pass
        elapsed = time.perf_counter() - t0
        nbytes = _nbytes(tensor)
        if log is not None and log.enabled:
            log.append(fn.__name__, kwargs.get("log_name", fn.__name__),
                       elapsed, nbytes)
        if tm_on:
            telemetry.record_comm(fn.__name__, nbytes, elapsed, axis=axis,
                                  wire_bytes=wire_bytes)
        return result

    return wrapper


# ---------------------------------------------------------------------------
# In-trace collectives (jax.lax over mesh axes)
# ---------------------------------------------------------------------------

@timed_op
def all_reduce(tensor, op=ReduceOp.SUM, axis_name="dp", **kwargs):
    """reference ``comm/comm.py:483`` all_reduce."""
    if op == ReduceOp.SUM:
        return lax.psum(tensor, axis_name)
    if op == ReduceOp.AVG:
        return lax.pmean(tensor, axis_name)
    if op == ReduceOp.MAX:
        return lax.pmax(tensor, axis_name)
    if op == ReduceOp.MIN:
        return lax.pmin(tensor, axis_name)
    if op == ReduceOp.PRODUCT:
        return jnp.exp(lax.psum(jnp.log(tensor), axis_name))
    raise ValueError(f"unknown reduce op {op}")


inference_all_reduce = all_reduce  # reference comm.py:500


@timed_op
def all_gather(tensor, axis_name="dp", axis=0, tiled=True, **kwargs):
    """reference ``comm/comm.py:228`` all_gather / :297 all_gather_into_tensor.

    ``tiled=True`` concatenates along ``axis`` (the into_tensor form);
    ``tiled=False`` stacks a new leading axis."""
    return lax.all_gather(tensor, axis_name, axis=axis, tiled=tiled)


all_gather_into_tensor = all_gather


@timed_op
def reduce_scatter(tensor, op=ReduceOp.SUM, axis_name="dp", scatter_dim=0, **kwargs):
    """reference ``comm/comm.py:446`` reduce_scatter / :246 reduce_scatter_fn.

    psum_scatter splits along ``scatter_dim`` across the axis; with
    ``op=AVG`` divides by the axis size."""
    if scatter_dim != 0:
        tensor = jnp.moveaxis(tensor, scatter_dim, 0)
    out = lax.psum_scatter(tensor, axis_name, scatter_dimension=0, tiled=True)
    if scatter_dim != 0:
        out = jnp.moveaxis(out, 0, scatter_dim)
    if op == ReduceOp.AVG:
        out = out / lax.axis_size(axis_name)
    return out


reduce_scatter_tensor = reduce_scatter


@timed_op
def all_to_all_single(tensor, axis_name="sp", split_axis=0, concat_axis=0, tiled=True, **kwargs):
    """reference ``comm/comm.py:331`` all_to_all_single."""
    return lax.all_to_all(tensor, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


@timed_op
def all_to_all(tensors, axis_name="sp", **kwargs):
    """reference ``comm/comm.py:350`` all_to_all (list form)."""
    stacked = jnp.stack(tensors, axis=0)
    out = lax.all_to_all(stacked, axis_name, split_axis=0, concat_axis=0, tiled=False)
    n = lax.axis_size(axis_name)
    return [out[i] for i in range(n)]


@timed_op
def broadcast(tensor, src=0, axis_name="dp", **kwargs):
    """reference ``comm/comm.py:222`` broadcast — keep src's value on all ranks."""
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == src, tensor, jnp.zeros_like(tensor))
    return lax.psum(masked, axis_name)


@timed_op
def reduce(tensor, dst=0, op=ReduceOp.SUM, axis_name="dp", **kwargs):
    """reference ``comm/comm.py:433`` reduce — SPMD has no single-destination
    reduce; result is materialized everywhere (dst kept for API parity)."""
    return all_reduce(tensor, op=op, axis_name=axis_name)


def send_recv(tensor, perm, axis_name="pp"):
    """Point-to-point via collective permute (reference ``runtime/pipe/p2p.py:46,67``
    send/recv pairs). ``perm`` is a list of (src, dst) pairs along ``axis_name``."""
    return lax.ppermute(tensor, axis_name, perm)


def send_next(tensor, axis_name="pp"):
    n = lax.axis_size(axis_name)
    return lax.ppermute(tensor, axis_name, [(i, (i + 1) % n) for i in range(n)])


def send_prev(tensor, axis_name="pp"):
    n = lax.axis_size(axis_name)
    return lax.ppermute(tensor, axis_name, [(i, (i - 1) % n) for i in range(n)])


def axis_rank(axis_name):
    return lax.axis_index(axis_name)


@timed_op
def gather(tensor, dst=0, axis_name="dp", axis=0, **kwargs):
    """reference ``comm/comm.py:380`` gather — SPMD materializes the gathered
    result on every device (XLA keeps it live only where used; ``dst`` kept
    for API parity)."""
    return lax.all_gather(tensor, axis_name, axis=axis, tiled=False)


@timed_op
def scatter(tensor, src=0, axis_name="dp", axis=0, **kwargs):
    """reference ``comm/comm.py:391`` scatter — each rank takes its slice of
    src's tensor (broadcast + static slice; XLA DCEs the unused shards)."""
    full = broadcast.__wrapped__(tensor, src=src, axis_name=axis_name) \
        if hasattr(broadcast, "__wrapped__") else broadcast(tensor, src=src,
                                                           axis_name=axis_name)
    idx = lax.axis_index(axis_name)
    n = lax.axis_size(axis_name)
    if full.shape[axis] % n != 0:
        raise ValueError(f"scatter: dim {axis} of size {full.shape[axis]} "
                         f"is not divisible by axis '{axis_name}' size {n}")
    size = full.shape[axis] // n
    return lax.dynamic_slice_in_dim(full, idx * size, size, axis=axis)


def monitored_barrier(group=None, timeout=None, **kwargs):
    """reference ``comm/comm.py:412`` — rank-failure detection is the
    launcher/elastic-agent's job on TPU; behaves as ``barrier``."""
    return barrier(group=group)


def _coalesce_by_dtype(tensors, exchange):
    """One fused exchange per dtype group (mixed buckets must come back in
    their own dtypes — concatenating across dtypes would silently promote).
    ``exchange(flat) -> exchanged flat`` may add leading dims."""
    groups = {}
    for i, t in enumerate(tensors):
        groups.setdefault(jnp.asarray(t).dtype, []).append(i)
    out = [None] * len(tensors)
    for dtype, idxs in groups.items():
        flat = jnp.concatenate([jnp.ravel(tensors[i]) for i in idxs])
        ex = exchange(flat)
        off = 0
        for i in idxs:
            shape = tensors[i].shape
            n = int(np.prod(shape)) if shape else 1
            out[i] = ex[..., off:off + n].reshape(ex.shape[:-1] + tuple(shape))
            off += n
    return out


@timed_op
def all_reduce_coalesced(tensors, op=ReduceOp.SUM, axis_name="dp", **kwargs):
    """reference ``comm/comm.py:512`` — fused exchange for a list of tensors
    (flatten-concat per dtype, one psum each, split)."""
    return _coalesce_by_dtype(
        tensors, lambda flat: all_reduce(flat, op=op, axis_name=axis_name))


@timed_op
def all_gather_coalesced(tensors, axis_name="dp", **kwargs):
    """reference ``comm/comm.py:475`` — gather a list of tensors in one
    exchange per dtype; returns per-tensor [world, ...] stacks."""
    return _coalesce_by_dtype(
        tensors, lambda flat: lax.all_gather(flat, axis_name, axis=0,
                                             tiled=False))


class _ImmediateHandle:
    """Async-handle parity (reference isend/irecv return works): XLA programs
    are scheduled asynchronously by dispatch, so wait() is a no-op."""

    def __init__(self, value=None):
        self.value = value

    def wait(self):
        return self.value

    def is_completed(self):
        return True


def isend(tensor, dst, src=0, axis_name="pp", **kwargs):
    """reference ``comm/comm.py:362``. SPMD point-to-point is a (src, dst)
    permute traced on every device — callers name both endpoints. The permute
    is issued into the XLA program immediately; the handle satisfies
    ``.wait()`` callers. Ranks other than ``dst`` receive zeros."""
    return _ImmediateHandle(send_recv(tensor, [(src, dst)], axis_name))


def irecv(tensor, src, dst=0, axis_name="pp", **kwargs):
    """reference ``comm/comm.py:370`` — same permute viewed from the
    receiver."""
    return _ImmediateHandle(send_recv(tensor, [(src, dst)], axis_name))


# ---------------------------------------------------------------------------
# Host-level process management
# ---------------------------------------------------------------------------

def discover_process_env(environ=None):
    """(coordinator, num_processes, process_id) from the environment —
    the reference's ``mpi_discovery`` (:673) + SLURM/launcher env paths,
    covering every ``launcher/multinode_runner.py`` backend:

    - explicit DST_*/MASTER_ADDR+RANK (ssh/local runners bake the rank),
    - SLURM (``srun``): SLURM_PROCID/SLURM_NTASKS/SLURM_JOB_NODELIST,
    - Open MPI (``mpirun``): OMPI_COMM_WORLD_RANK/SIZE,
    - MPICH/Intel MPI hydra: PMI_RANK/PMI_SIZE,
    - PDSH (rankless): this host's position in the broadcast DS_WORLD_INFO.
    """
    env = os.environ if environ is None else environ
    coordinator = env.get("DST_COORDINATOR_ADDRESS") or env.get("MASTER_ADDR")
    num_proc = int(env.get("DST_NUM_PROCESSES", env.get("WORLD_SIZE", "1")))
    if "DST_PROCESS_ID" in env or "RANK" in env:
        return coordinator, num_proc, int(env.get("DST_PROCESS_ID",
                                                  env.get("RANK", "0")))
    # SLURM discovery (reference comm.py:673 mpi_discovery analog)
    if "SLURM_PROCID" in env:
        num_proc = int(env.get("SLURM_NTASKS", num_proc))
        coordinator = coordinator or env.get(
            "SLURM_JOB_NODELIST", "localhost").split(",")[0]
        return coordinator, num_proc, int(env["SLURM_PROCID"])
    if coordinator is None and "SLURM_JOB_NODELIST" in env:
        return (env["SLURM_JOB_NODELIST"].split(",")[0],
                int(env.get("SLURM_NTASKS", "1")),
                int(env.get("SLURM_PROCID", "0")))
    # mpirun discovery: Open MPI then hydra-family (MPICH/IMPI/MVAPICH)
    if "OMPI_COMM_WORLD_RANK" in env:
        return (coordinator, int(env.get("OMPI_COMM_WORLD_SIZE", num_proc)),
                int(env["OMPI_COMM_WORLD_RANK"]))
    if "PMI_RANK" in env:
        return (coordinator, int(env.get("PMI_SIZE", num_proc)),
                int(env["PMI_RANK"]))
    # PDSH: no scheduler rank — derive it from this node's hostname position
    # in the world info the launcher broadcast
    if "DS_WORLD_INFO" in env:
        import socket
        from deepspeed_tpu.launcher.runner import decode_world_info
        hosts = list(decode_world_info(env["DS_WORLD_INFO"]))
        if len(hosts) > 1:
            hostname = socket.gethostname()
            for h in (hostname, hostname.split(".")[0]):
                if h in hosts:
                    return coordinator, len(hosts), hosts.index(h)
            # defaulting to rank 0 here would make EVERY unmatched node claim
            # rank 0 and hang the coordinator with no diagnostic
            raise RuntimeError(
                f"rank discovery: hostname {hostname!r} not found in the "
                f"launcher's world info {hosts} — use hostfile names matching "
                f"`hostname` (or a scheduler launcher that assigns ranks)")
    return coordinator, num_proc, 0


def init_distributed(dist_backend=None,
                     auto_mpi_discovery=True,
                     distributed_port=29500,
                     verbose=True,
                     timeout=None,
                     init_method=None,
                     dist_init_required=None,
                     config=None,
                     rank=-1,
                     world_size=-1):
    """Bring up multi-host JAX (reference ``comm/comm.py:604`` init_distributed).

    The reference discovers ranks from MPI/AzureML/SLURM env (:650-771) and
    calls torch.distributed.init_process_group; here the equivalent is
    ``jax.distributed.initialize`` which reads the coordinator address. On a
    single host this is a no-op.
    """
    global _initialized
    if _initialized:
        return
    # worker-startup fault point: lets drills kill a worker exactly where a
    # bad host dies in production (before joining the gang)
    _faults.maybe_fail("worker.exit")
    coordinator, num_proc, proc_id = discover_process_env()
    # the launcher's env contract (launcher/runner.py node_env) carries the port
    distributed_port = int(os.environ.get("MASTER_PORT", distributed_port))
    # explicit arguments override discovery (reference init_distributed
    # rank/world_size params)
    if rank >= 0:
        proc_id = rank
    if world_size > 0:
        num_proc = world_size
    if coordinator is not None and num_proc > 1:
        if verbose:
            logger.info(f"init_distributed: coordinator={coordinator}:{distributed_port} "
                        f"process {proc_id}/{num_proc}")
        # coordinator bring-up races with worker starts across the gang —
        # absorb transient connect failures with the shared backoff policy
        from deepspeed_tpu.utils.retry import retry_call
        retry_call(
            jax.distributed.initialize, retries=3, base_delay=1.0,
            max_delay=15.0, retry_on=(RuntimeError, OSError, ValueError),
            on_retry=lambda a, e, d: logger.warning(
                f"init_distributed attempt {a} failed ({e}); "
                f"retrying in {d:.1f}s"),
            coordinator_address=f"{coordinator}:{distributed_port}",
            num_processes=num_proc,
            process_id=proc_id)
    _initialized = True


def is_initialized():
    return _initialized


def get_rank(group=None):
    return jax.process_index()


def get_world_size(group=None):
    return jax.process_count()


def get_local_rank():
    return int(os.environ.get("DST_LOCAL_RANK", os.environ.get("LOCAL_RANK", "0")))


def barrier(group=None, **kwargs):
    """Host-level process barrier (reference ``comm/comm.py:406``)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("deepspeed_tpu_barrier")


monitored_barrier = barrier


def log_summary(show_straggler=False):
    """Print the comms-log summary (reference ``comm/comm.py`` log_summary).
    When telemetry is enabled its per-axis comm table (which also covers
    traced in-jit collectives) is printed alongside the host-level one."""
    out = get_comms_logger().log_all()
    from deepspeed_tpu import telemetry
    if telemetry.enabled():
        telemetry.log_summary()
    return out

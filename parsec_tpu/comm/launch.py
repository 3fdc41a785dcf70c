"""Multiprocess SPMD launcher for distributed runs and tests.

The mpiexec analog (reference: tests run distributed cases under
``${MPI_TEST_CMD_LIST} <nranks>`` = mpiexec -n N on one node,
CMakeLists.txt:921-952): spawns N python processes, wires each into a
SocketCE + RemoteDepEngine + Context, runs ``fn(ctx, rank, nranks)``
SPMD, and gathers per-rank results (or the first traceback).

Children force jax onto CPU so distributed tests run anywhere,
mirroring the reference's multi-process-on-one-node strategy — and
because a chip belongs to ONE process at a time: a parent that has
touched JAX holds it, and a rank that reached for it would fail or
hang.  ``PARSEC_LAUNCH_PLATFORM=tpu`` lets a rank take the chip, which
can only work when the launching process never initialised a JAX
backend (and with one rank per chip).
"""

from __future__ import annotations

import multiprocessing as mp
import os
import random
import socket
import traceback
from typing import Any, Callable, List, Optional


def _probe_port_base(nranks: int, tries: int = 32) -> int:
    """Pick a base port with every rank's port currently bindable: an
    in-use port would make SocketCE.bind fail or cross-talk with an
    unrelated listener (ADVICE r1 low)."""
    for _ in range(tries):
        base = random.randrange(20000, 60000 - nranks)
        socks = []
        try:
            for r in range(nranks):
                s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                s.bind(("127.0.0.1", base + r))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    return random.randrange(20000, 60000 - nranks)


def _worker(rank: int, nranks: int, port_base: int, nb_cores: int,
            fn: Callable, args: tuple, outq) -> None:
    os.environ.setdefault("PARSEC_COMM_PORT_BASE", str(port_base))
    platform = os.environ.get("PARSEC_LAUNCH_PLATFORM", "cpu")
    os.environ["JAX_PLATFORMS"] = platform
    try:
        try:
            import jax
            jax.config.update("jax_platforms", platform)
        except Exception:
            pass
        from parsec_tpu.comm.engine import make_ce
        from parsec_tpu.comm.remote_dep import RemoteDepEngine
        from parsec_tpu.core.context import Context

        # transport selected by PARSEC_MCA_COMM_TRANSPORT (inherited by
        # the spawned children): evloop (default) or threads (the old
        # per-peer-thread path, kept for A/B attribution)
        ce = make_ce(rank, nranks, port_base)
        # A fault plan's clock (kill_rank=<r>@t+<s>s) starts when user
        # code does, at the start-up barrier below, not when the
        # transport came up: bringing the Context up and meeting the
        # barrier takes 0.2-0.95 s from run to run and more on a loaded
        # host, and with that inside the clock a kill "at t+1.0s" fired
        # before the victim had run one task in 11 of 20 runs (PR 25),
        # or inside start-up, where it breaks the barrier.  (The
        # transport still comes up first: its listener must not wait
        # for the Context, peers dial it against a deadline.)
        ce._arm_kill(hold=True)
        ctx = Context(nb_cores=nb_cores, rank=rank, nranks=nranks)
        rde = RemoteDepEngine(ce, ctx)
        ce.barrier()   # every rank's handlers are wired before user code
        ce._arm_kill()
        try:
            result = fn(ctx, rank, nranks, *args)
            ce.barrier()
            # past the final barrier every rank is done: peers closing
            # their sockets now (possibly while we still serialize the
            # result below) is orderly shutdown, not a failure
            ce._stop = True
            outq.put((rank, None, result))
        finally:
            ce._stop = True
            ctx.fini()
            rde.fini()
    except Exception:
        outq.put((rank, traceback.format_exc(), None))


def run_distributed(fn: Callable, nranks: int, args: tuple = (),
                    nb_cores: int = 2, timeout: float = 120.0,
                    port_base: Optional[int] = None,
                    tolerate_ranks=()) -> List[Any]:
    """Run ``fn(ctx, rank, nranks, *args)`` on ``nranks`` processes;
    returns the per-rank results in rank order.

    ``tolerate_ranks``: ranks whose failure is EXPECTED (chaos kill
    victims under recovery — the survivors' completion is the result
    that matters); their slot in the returned list is None when they
    errored.  An error on any other rank still fails the run."""
    if port_base is None:
        port_base = _probe_port_base(nranks)
    mpctx = mp.get_context("spawn")
    outq = mpctx.Queue()
    procs = [mpctx.Process(target=_worker,
                           args=(r, nranks, port_base, nb_cores, fn, args,
                                 outq),
                           daemon=True)
             for r in range(nranks)]
    # Children must NOT reach for the accelerator: the chip admits one
    # process, and this one may already hold it (module docstring).  Env
    # is inherited at spawn — patch, start, restore.
    saved = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = \
        os.environ.get("PARSEC_LAUNCH_PLATFORM", "cpu")
    try:
        for p in procs:
            p.start()
    finally:
        if saved is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = saved
    tolerate = set(tolerate_ranks)
    results: dict = {}
    errors: List[str] = []
    try:
        for _ in range(nranks):
            rank, err, res = outq.get(timeout=timeout)
            if err is not None and rank in tolerate:
                results[rank] = None   # expected casualty (chaos kill)
            elif err is not None:
                errors.append(f"rank {rank}:\n{err}")
            else:
                results[rank] = res
    except Exception as exc:
        for p in procs:
            p.terminate()
        raise TimeoutError(
            f"distributed run incomplete ({len(results)}/{nranks} ranks): "
            f"{errors or exc}")
    finally:
        for p in procs:
            p.join(timeout=10)
            if p.is_alive():
                p.terminate()
    if errors:
        raise RuntimeError("distributed run failed:\n" + "\n".join(errors))
    return [results[r] for r in range(nranks)]

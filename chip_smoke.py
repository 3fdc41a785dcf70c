#!/usr/bin/env python3
"""The quickest proof that the main path still starts on the chip.

Drives ``Context`` -> PTG taskpool -> dep engine -> ``XlaDevice`` once
per app, at the sizes the repo calls its headline, in ONE process on ONE
TPU chip, and checks every result.  The phases are the benchmark's own
jobs (``benchmark/apps/``: operands, MCA settings and limits from
``benchmark/configs/``, the comparison ``Job.check()``), at the sizes of
its cells 2, 1 and 5; nothing under ``benchmark/`` imports this file:

    gemm    dplasma_gemm_bf16 at mb=12288, 3x3 tiles, kt=4: every C tile
            against the plain product, c_rel_err <= 1e-4
    potrf   dplasma_potrf_bf16 at mb=6144, nt=16 (n = 98 304, ~10 GB
            resident): one warm pass, two runs, offdiag_resid <= 0.02
    geqrf   dplasma_geqrf_bf16 at mb=6144, nt=8 (n = 49 152), ib=512,
            through the default path (column chains two links a
            program): factor_resid and below_diag_max under the
            configuration's limits

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # ONLY the four-chip phase and the
                                      # one-chip runs it is compared with

Each phase prints one JSON line (sizes, storage dtype, set-up and run
seconds, accuracy, every device's counters, peak HBM, native extensions);
the LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
The script exits non-zero — and prints no such line — unless JAX reports
a TPU, all four native extensions built and loaded, no fused width
failed to compile, no device faulted and every accuracy bound held.
Seconds printed here are smoke output, not benchmark results.

Tiles are born on the device from ``--seed``; nothing is read from disk
or the network.  The phases are plain functions of their sizes
(tests/test_chip_smoke.py runs them tiny on CPU devices); only
``main()`` insists on the TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import numpy as np

from benchmark import tiles
from benchmark.apps import (gemm as gemm_app, geqrf as geqrf_app,
                            potrf as potrf_app)
from parsec_tpu.utils.mca import params

ROOT = os.path.dirname(os.path.abspath(__file__))

#: largest relative difference allowed between a sampled potrf tile of
#: the four-chip run and of the one-chip run: two bfloat16 spacings
POTRF_AGREE_TOL = 1e-2


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class SmokeFailure(RuntimeError):
    """A phase ran to its end and what came out is wrong."""


@contextlib.contextmanager
def _mca(**values):
    """Pin MCA parameters for a phase."""
    for k, v in values.items():
        params.set(k, v)
    try:
        yield
    finally:
        for k in values:
            params.unset(k)


def native_extensions() -> dict:
    """Which of the four native artifacts built and loaded."""
    from parsec_tpu import native
    return {"libparsec_tpu": native.load() is not None,
            "schedext": native.load_schedext() is not None,
            "pinsext": native.load_pinsext() is not None,
            "commext": native.load_commext() is not None}


def _config(name: str, storage=None) -> dict:
    """The benchmark's configuration ``name`` as committed; ``storage``
    replaces its storage dtype (the CPU rehearsal: XLA's CPU backend has
    no bf16 x bf16 -> f32 dot)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    return {**config, "storage": storage} if storage else config


def _run_passes(ctx, passes: int, t0: float, stage, pool, *Ms):
    """``passes`` times: ``stage()`` the operands (set-up, off the
    clock), then run ``pool()`` to its end and wait for the tiles of
    ``Ms``.  Returns (set-up seconds, seconds of each later pass):
    set-up is everything from ``t0`` to the end of the FIRST pass — tile
    generation, every first-use compile, and the background fused-width
    compiles, which it waits out."""
    from parsec_tpu.devices.xla import wait_fuse_warm
    run_s = []
    for p in range(passes):
        stage()
        t1 = time.perf_counter()
        ctx.add_taskpool(pool())
        ctx.wait()
        tiles.fence(*Ms)
        if p == 0:
            wait_fuse_warm()
            setup_s = time.perf_counter() - t0
        else:
            run_s.append(time.perf_counter() - t1)
    return setup_s, run_s


def _device_report(ctx, *Ms) -> list:
    """Per attached device: counters, failed fused widths, peak HBM and
    how many of the phase's tiles it holds the newest copy of."""
    out = []
    for d in ctx.device_registry.accelerators:
        held = 0
        for M in Ms:
            for m, n in M.local_tiles():
                datum = M.data_of(m, n)
                c = datum.copies().get(d.space)
                if c is not None and c.payload is not None \
                        and c.version == datum.newest_version():
                    held += 1
        mem = d.jdev.memory_stats() or {}
        out.append({"name": d.name, "stats": d.stats.as_dict(),
                    "fuse_failures": {f"{k}x{w}": v for (k, w), v
                                      in d.fuse_failures.items()},
                    "tiles_held": held,
                    "peak_bytes_in_use": mem.get("peak_bytes_in_use")})
    return out


def _require_healthy(phase: str, devices: list, every_device: bool) -> None:
    for d in devices:
        if d["fuse_failures"]:
            raise SmokeFailure(f"{phase}: {d['name']} has fused widths "
                               f"that failed to compile: "
                               f"{d['fuse_failures']}")
        if d["stats"]["faults"]:
            raise SmokeFailure(f"{phase}: {d['name']} reports "
                               f"{d['stats']['faults']} faults")
        if every_device and not (d["stats"]["executed_tasks"] > 0
                                 and d["tiles_held"] > 0):
            raise SmokeFailure(f"{phase}: {d['name']} sat idle "
                               f"(executed_tasks="
                               f"{d['stats']['executed_tasks']}, "
                               f"tiles_held={d['tiles_held']})")
    if not any(d["stats"]["executed_tasks"] for d in devices):
        raise SmokeFailure(f"{phase}: no device executed a task")


def _ici_stats(ctx) -> dict:
    return ctx.ici.stats.as_dict() if ctx.ici is not None else {}


def _to_host(t) -> np.ndarray:
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(t).astype(jnp.float32))


# ---------------------------------------------------------------------------
# gemm and potrf: the benchmark's jobs
# ---------------------------------------------------------------------------

def _run_job(phase: str, Job, config: dict, traffic: dict, seed: int,
             passes: int, samples: int, every_device: bool,
             prepare=None) -> dict:
    """One phase on a job of the benchmark (``benchmark/apps/``): the
    configuration's MCA settings, ``passes`` runs (the first is the warm
    one), then ``Job.check()`` on what the last left, held to the
    configuration's limits as the benchmark's harness holds it.
    ``prepare(job, ctx)`` may lay the matrices out and return another
    taskpool builder; ``samples`` lower tiles of the output, drawn from
    the seed, come back on the host under ``_samples``."""
    from parsec_tpu.core.context import Context

    t0 = time.perf_counter()
    with _mca(**config["mca"]), Context(nb_cores=4) as ctx:
        job = Job(config, traffic, ctx, seed)
        pool = prepare(job, ctx) if prepare else job.pool
        result = job.outputs[0]
        lower = [t for t in result.local_tiles() if t[0] >= t[1]]
        picked = np.random.default_rng(seed).choice(
            len(lower), size=min(samples, len(lower)), replace=False)
        try:
            job.setup()
            setup_s, run_s = _run_passes(ctx, passes, t0, job.stage, pool,
                                         *job.outputs)
            t1 = time.perf_counter()
            checked = job.check()
            out = {"phase": phase, **traffic, "storage": config["storage"],
                   "distributed": every_device, "mca": config["mca"],
                   "setup_s": round(setup_s, 3),
                   "run_s": [round(t, 3) for t in run_s],
                   "check_s": round(time.perf_counter() - t1, 3),
                   "compared": {k: {"value": v, "limit": job.limits[k]}
                                for k, v in checked["numbers"].items()},
                   "notes": checked["notes"],
                   "devices": _device_report(ctx, *job.outputs),
                   "ici": _ici_stats(ctx)}
            kept = {lower[i]: _to_host(tiles.newest(result, *lower[i]))
                    for i in sorted(picked)}
        finally:
            job.drop()
    del job, result
    gc.collect()
    over = {k: c for k, c in out["compared"].items()
            if not c["value"] <= c["limit"]}
    if over or not out["compared"]:
        raise SmokeFailure(f"{phase}: over the configuration's limits: "
                           f"{over}: {out}")
    _require_healthy(phase, out["devices"], every_device)
    if samples:
        out["_samples"] = kept
    return out


def run_gemm(mb: int, mt: int, nt: int, kt: int, seed: int = 0,
             storage=None, passes: int = 2, distribute: bool = False,
             panel_bcast=None, samples: int = 0) -> dict:
    """C += A B: the ``dplasma_gemm_bf16`` job at this size, every C
    tile against the plain product.  ``distribute`` spreads all three
    matrices over every attached device and ``panel_bcast`` broadcasts
    the panels (no configuration of the benchmark does either yet)."""
    def prepare(job, ctx):
        Ms = (job.A, job.B, job.C)
        if distribute:
            for M in Ms:
                M.distribute_devices(ctx)
        if not panel_bcast:
            return job.pool
        from parsec_tpu.apps.gemm import gemm_taskpool
        return lambda: gemm_taskpool(*Ms, panel_bcast=True)

    return _run_job("gemm", gemm_app.Job,
                    _config("dplasma_gemm_bf16", storage),
                    {"m": mt * mb, "n": nt * mb, "k": kt * mb, "mb": mb},
                    seed, passes, samples, distribute, prepare)


def run_potrf(mb: int, nt: int, seed: int = 0, storage=None,
              passes: int = 3, distribute: bool = False,
              samples: int = 0) -> dict:
    """Tiled Cholesky of n = nt*mb: the ``dplasma_potrf_bf16`` job, its
    four-chip configuration (2x2 block-cyclic) under ``distribute``."""
    return _run_job("potrf", potrf_app.Job,
                    _config("dplasma_potrf_bf16_4chip" if distribute
                            else "dplasma_potrf_bf16", storage),
                    {"n": nt * mb, "mb": mb}, seed, passes, samples,
                    distribute)


# ---------------------------------------------------------------------------
# geqrf
# ---------------------------------------------------------------------------

def run_geqrf(mb: int, nt: int, ib: int = 512, seed: int = 0,
              storage=None, passes: int = 2) -> dict:
    """Tiled QR of n = nt*mb: the ``dplasma_geqrf_bf16`` job (general
    random operand, ib-blocked panel engine, default chain fusion), R
    held to R^T R = A^T A on a probe and to zeros under the diagonal."""
    try:
        return _run_job("geqrf", geqrf_app.Job,
                        _config("dplasma_geqrf_bf16", storage),
                        {"n": nt * mb, "mb": mb, "ib": ib}, seed, passes,
                        0, False)
    except ValueError as exc:       # an ib the panel engine would clamp
        raise SmokeFailure(str(exc)) from exc


# ---------------------------------------------------------------------------
# several chips
# ---------------------------------------------------------------------------

def run_ici_ring(mb: int, seed: int = 0) -> dict:
    """One full-size tile per device sent round the ring through
    ``ctx.ici.permute`` — the shard_map/ppermute program over real
    device-to-device links — and one tile replicated to every device
    through ``ctx.ici.bcast``; every arrival is compared with what was
    sent."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic

    t0 = time.perf_counter()
    with Context(nb_cores=2) as ctx:
        ici = ctx.ici
        if ici is None:
            raise SmokeFailure("ici: one device attached, no ICI engine")
        devs = ici.xla_devices
        nd = len(devs)
        T = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mb, ln=mb, name="ring",
                              dtype=tiles.storage_dtype("bfloat16"))
        sent = [tiles.make_tile(T, seed, i, 0, device=d.jdev)
                for i, d in enumerate(devs)]
        got = ici.permute([(devs[i].space, devs[(i + 1) % nd].space, sent[i])
                           for i in range(nd)])
        for i in range(nd):
            dst = devs[(i + 1) % nd]
            arr = got[(devs[i].space, dst.space)]
            if dst.jdev not in arr.devices():
                raise SmokeFailure(f"ici: ring tile {i} landed on "
                                   f"{arr.devices()}, not {dst.name}")
            if not bool(jnp.array_equal(
                    arr, jax.device_put(sent[i], dst.jdev))):
                raise SmokeFailure(f"ici: ring tile {i} arrived changed")
        reps = ici.bcast(sent[0], [d.space for d in devs])
        if len(reps) != nd:
            raise SmokeFailure(f"ici: bcast reached {len(reps)}/{nd}")
        for d in devs:
            if not bool(jnp.array_equal(
                    reps[d.space], jax.device_put(sent[0], d.jdev))):
                raise SmokeFailure(f"ici: bcast replica on {d.name} differs")
        stats = ici.stats.as_dict()
    del sent, got, reps
    gc.collect()
    if not (stats["permutes"] > 0 and stats["bcasts"] > 0):
        raise SmokeFailure(f"ici: no collective ran: {stats}")
    return {"phase": "ici_ring", "mb": mb, "devices": nd, "ici": stats,
            "run_s": round(time.perf_counter() - t0, 3)}


def _agree(name: str, many: dict, one: dict, tol: float) -> float:
    """Largest relative difference between the sampled tiles of the
    several-device run and of the one-device run."""
    worst = 0.0
    for t, a in many["_samples"].items():
        b = one["_samples"][t]
        worst = max(worst, float(np.abs(a - b).max())
                    / max(float(np.abs(b).max()), 1e-30))
    if not worst <= tol:
        raise SmokeFailure(f"{name}: sampled tiles of the distributed and "
                           f"the one-device run differ by {worst:.3e} "
                           f"(> {tol})")
    return worst


def run_multichip(potrf_size: dict, gemm_size: dict, seed: int = 0,
                  emit=lambda line: None) -> dict:
    """The same potrf problem and a panel_bcast GEMM, once spread over
    every attached device (``distribute_devices``) and once confined to
    one device in a second Context (``device_max=1``), compared."""
    ring = run_ici_ring(potrf_size["mb"], seed)
    emit(ring)
    runs = {}
    for label, scope in (("many", {}), ("one", {"device_max": 1})):
        with _mca(**scope):
            dist = label == "many"
            p = run_potrf(**potrf_size, seed=seed, passes=1,
                          distribute=dist, samples=3)
            g = run_gemm(**gemm_size, seed=seed, passes=1, distribute=dist,
                         panel_bcast=True, samples=2)
        if dist:
            moved = p["ici"]
            if not moved or moved["bcasts"] + moved["puts"] \
                    + moved["permutes"] <= 0:
                raise SmokeFailure(f"potrf over several devices moved "
                                   f"nothing over ICI: {moved}")
            # on a 2 x 2 grid a row or column panel has ONE other chip
            # to reach (a put); broadcasts start at three
            if not g["ici"] or g["ici"]["bcasts"] + g["ici"]["puts"] \
                    + g["ici"]["permutes"] <= 0:
                raise SmokeFailure(f"panel_bcast GEMM over several devices "
                                   f"moved no panel over ICI: {g['ici']}")
        runs[label] = (p, g)
        for r in (p, g):
            emit({**{k: v for k, v in r.items() if k != "_samples"},
                  "scope": label})
    (p4, g4), (p1, g1) = runs["many"], runs["one"]

    def value(r, number):
        return r["compared"][number]["value"]
    return {"phase": "multichip",
            "potrf_offdiag_resid": {"many": value(p4, "offdiag_resid"),
                                    "one": value(p1, "offdiag_resid")},
            "potrf_tiles_rel_diff": _agree("potrf", p4, p1,
                                           POTRF_AGREE_TOL),
            "gemm_c_rel_err": {"many": value(g4, "c_rel_err"),
                               "one": value(g1, "c_rel_err")},
            # the same quantity as c_rel_err, so the same limit
            "gemm_tiles_rel_diff": _agree(
                "gemm", g4, g1, g1["compared"]["c_rel_err"]["limit"]),
            "ici_ring": ring["ici"]}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the four-chip phase and the one-chip "
                         "runs it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the device-side tile generators")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        log(f"chip_smoke: found no TPU — JAX reports {device}; this "
            f"script proves the chip path and does not run on a CPU")
        return 2
    if len(devs) < args.chips:
        log(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
            f"JAX reports {len(devs)}")
        return 2
    native = native_extensions()
    if not all(native.values()):
        log(f"chip_smoke: native extensions missing: {native} (the "
            f"pure-Python fallback would hide a build fault)")
        return 3
    from parsec_tpu.devices import configure_compile_cache
    cache_dir = configure_compile_cache()

    def emit(line: dict) -> None:
        print(json.dumps({**line, "native": native}), flush=True)

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    t0 = time.perf_counter()
    log(f"chip_smoke: {device}, compile cache {cache_dir} "
        f"({cache_entries()} entries)")
    potrf_size = {"mb": 6144, "nt": 16}
    gemm_size = {"mb": 12288, "mt": 3, "nt": 3, "kt": 4}
    try:
        if args.chips == 4:
            emit(run_multichip(potrf_size, gemm_size, args.seed, emit))
        else:
            # one chip, however many the host has
            with _mca(device_max=1):
                emit(run_gemm(**gemm_size, seed=args.seed))
                potrf = run_potrf(**potrf_size, seed=args.seed)
                emit(potrf)
                st = potrf["devices"][0]["stats"]
                if not (st["fused_launches"] and st["chained_launches"]):
                    raise SmokeFailure(
                        f"potrf: launch fusion never engaged at nt="
                        f"{potrf_size['nt']}: {st}")
                geqrf = run_geqrf(mb=6144, nt=8, seed=args.seed)
                emit(geqrf)
                if not geqrf["devices"][0]["stats"]["chained_launches"]:
                    raise SmokeFailure(
                        f"geqrf: chain fusion never engaged: "
                        f"{geqrf['devices'][0]['stats']}")
    except SmokeFailure as exc:
        log(f"chip_smoke: FAILED: {exc}")
        return 1
    log(f"chip_smoke: passed in {time.perf_counter() - t0:.0f}s, compile "
        f"cache now {cache_entries()} entries")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

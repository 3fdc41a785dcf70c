#!/usr/bin/env python3
"""The quickest proof that the main path still starts on the chip.

Drives ``Context`` -> PTG taskpool -> dep engine -> ``XlaDevice`` once
per app, at the sizes the repo calls its headline, in ONE process on ONE
TPU chip, and checks every result by the repo's own means:

    gemm    gemm_taskpool, mb=12288, 3x3 tiles, kt=4, bf16 A/B, f32 C,
            sampled C tiles against a plain jnp product of the same tiles
    potrf   potrf_taskpool, mb=6144, nt=16, bf16 storage (n = 98 304,
            ~10 GB resident): one warm pass, two runs,
            apps/potrf_check.backward_error <= 1e-2
    geqrf   qr_taskpool as it runs by default (ib=512 panel engine,
            cross-panel chain fusion), mb=6144, bf16 storage, nt cut
            from 8 to 2 (see GEQRF_NT),
            apps/qr_check.factorization_residual <= 2e-2
    geqrf_per_kernel
            the same at the uncut nt=8 with device_fuse_panel=0: the
            panel engine at its headline size, one launch a panel kernel

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

import bench
from parsec_tpu.apps.potrf_check import _tile as _newest
from parsec_tpu.utils.mca import params

#: accuracy bounds (BENCH.md: the bf16-storage class measured in r5 was
#: 3.0e-3 for potrf and 1.0e-2 for geqrf)
GEMM_TOL = 1e-4
POTRF_TOL = 1e-2
GEQRF_TOL = 2e-2

#: run settings bench.py's potrf/geqrf modes use (bench._potrf_headline,
#: bench.main): the defaults overflow a 16 GB chip at these sizes
POTRF_MCA = {"device_fuse": 8, "device_runahead": 48,
             "device_inflight_depth": 32}
GEQRF_MCA = {"device_fuse": 8, "device_runahead": 20,
             "device_inflight_depth": 12, "device_fuse_window_ms": 4.0}
#: nt of the default-path geqrf phase, cut from the headline 8; mb is
#: not cut.  Chain fusion traces the held GEQRT/TSQRT links into their
#: consumer's program, so every distinct (chain, wave) shape compiles
#: GEQRT (96 s at mb=6144, ib=512) or TSQRT (159 s) once more per link
#: (sandbox compile, PERF.md PR 21).  nt=2 asks for about three such
#: programs, nt=3 for twice that, nt=8 for dozens: 2 is the largest nt
#: whose cold run leaves this script inside its 1200 s
GEQRF_NT = 2
#: the extra geqrf phase keeps nt=8 and turns chain fusion off: GEQRT and
#: TSQRT then compile once each, whatever nt
GEQRF_PER_KERNEL = {"device_fuse_panel": 0}


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


def _bf16():
    import ml_dtypes
    return ml_dtypes.bfloat16


def _sync_tiles(*Ms) -> None:
    """Block until every tile's newest payload has materialized (tile by
    tile: the tiles of a distributed matrix sit on different devices, so
    no single program may take them all)."""
    import jax
    for M in Ms:
        for m, n in M.local_tiles():
            p = _newest(M, m, n)
            if not isinstance(p, np.ndarray):
                jax.block_until_ready(p)


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
        _sync_tiles(*Ms)
        if p == 0:
            wait_fuse_warm()
            setup_s = time.perf_counter() - t0
        else:
            run_s.append(time.perf_counter() - t1)
    return setup_s, run_s


def _drop(ctx, *Ms) -> None:
    """Free the phase's device memory: tiles and arena scratch go
    without writeback (they are synthetic and checked already)."""
    bench._discard_device_tiles(*Ms)
    bench._discard_device_scratch(ctx)


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


def _sample(rng, tiles, k):
    idx = rng.choice(len(tiles), size=min(k, len(tiles)), replace=False)
    return [tiles[i] for i in sorted(idx)]


def _to_host(t) -> np.ndarray:
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(t).astype(jnp.float32))


# ---------------------------------------------------------------------------
# gemm
# ---------------------------------------------------------------------------

def run_gemm(mb: int, mt: int, nt: int, kt: int, seed: int = 0,
             ab_dtype=None, passes: int = 2, distribute: bool = False,
             panel_bcast=None, samples: int = 2,
             keep_samples: bool = False) -> dict:
    """C += A @ B through gemm_taskpool, tiles born on the device;
    ``samples`` C tiles are checked against a plain jnp product of the
    same A/B/C tiles.  ``distribute`` spreads all three matrices over
    every attached device."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.apps.gemm import gemm_taskpool
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic

    ab_dtype = ab_dtype or _bf16()
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mt * mb, ln=kt * mb, name="A",
                          dtype=ab_dtype)
    B = TwoDimBlockCyclic(mb=mb, nb=mb, lm=kt * mb, ln=nt * mb, name="B",
                          dtype=ab_dtype)
    C = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mt * mb, ln=nt * mb, name="C")
    s0 = 100003 * seed
    seeds = {"A": s0, "B": s0 + 10007, "C": s0 + 20011}
    rng = np.random.default_rng(seed)
    picked = _sample(rng, list(C.local_tiles()), samples)

    t0 = time.perf_counter()
    with Context(nb_cores=4) as ctx:
        if distribute:
            for M in (A, B, C):
                M.distribute_devices(ctx)
        for M in (A, B):
            bench.prestage(M, ctx, rand_scale=1.0, seed0=seeds[M.name])
        # C accumulates: give every pass the same C0, so the last pass
        # leaves C0 + A @ B whatever the number of passes
        setup_s, run_s = _run_passes(
            ctx, passes, t0,
            lambda: bench.prestage(C, ctx, rand_scale=1.0,
                                   seed0=seeds["C"]),
            lambda: gemm_taskpool(A, B, C, panel_bcast=panel_bcast), C)

        gen_c = bench._tile_generator(C, 1.0)
        lin = {t: i for i, t in enumerate(C.local_tiles())}

        @jax.jit
        def ref_err(got, c0, a_row, b_col):
            ref = c0
            for a, b in zip(a_row, b_col):
                ref = ref + jnp.matmul(a, b, preferred_element_type=c0.dtype)
            return jnp.max(jnp.abs(got - ref)), jnp.max(jnp.abs(ref))

        err = 0.0
        kept = {}
        for (m, n) in picked:
            got = _newest(C, m, n)
            here = next(iter(got.devices()))

            def put(t):
                return jax.device_put(jnp.asarray(t), here)
            num, den = ref_err(
                got, put(gen_c(float(seeds["C"] + lin[(m, n)]), 0.0)),
                [put(_newest(A, m, k)) for k in range(kt)],
                [put(_newest(B, k, n)) for k in range(kt)])
            err = max(err, float(num) / max(float(den), 1e-30))
            if keep_samples:
                kept[(m, n)] = _to_host(got)
        devices = _device_report(ctx, A, B, C)
        ici = _ici_stats(ctx)
        _drop(ctx, A, B, C)
    del A, B, C
    gc.collect()
    out = {"phase": "gemm", "mb": mb, "tiles": [mt, nt, kt],
           "ab_dtype": np.dtype(ab_dtype).name, "c_dtype": "float32",
           "distributed": distribute, "panel_bcast": bool(panel_bcast),
           "setup_s": round(setup_s, 3),
           "run_s": [round(t, 3) for t in run_s],
           "rel_err_vs_jnp": err, "checked_tiles": [list(t) for t in picked],
           "devices": devices, "ici": ici}
    if err > GEMM_TOL or not np.isfinite(err):
        raise SmokeFailure(f"gemm: sampled C tiles differ from the jnp "
                           f"product by {err:.3e} (> {GEMM_TOL}): {out}")
    _require_healthy("gemm", devices, every_device=distribute)
    if keep_samples:
        out["_samples"] = kept
    return out


# ---------------------------------------------------------------------------
# potrf
# ---------------------------------------------------------------------------

def run_potrf(mb: int, nt: int, seed: int = 0, mp: bool = True,
              passes: int = 3, distribute: bool = False,
              samples: int = 3, keep_samples: bool = False) -> dict:
    """Tiled Cholesky of an n = nt*mb SPD matrix born on the device (the
    bench's matrix: iota tiles, dominant diagonal): ``passes`` full
    factorizations (the first is the warm one), then the exact backward
    error of the last."""
    from parsec_tpu.apps.potrf import potrf_taskpool
    from parsec_tpu.apps.potrf_check import backward_error
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic

    dtype = _bf16() if mp else np.float32
    n = nt * mb
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n, name="A", dtype=dtype)
    s0 = 1009 * seed
    lower = [t for t in A.local_tiles() if t[0] >= t[1]]
    picked = _sample(np.random.default_rng(seed), lower, samples)

    t0 = time.perf_counter()
    with _mca(**POTRF_MCA), Context(nb_cores=4) as ctx:
        if distribute:
            A.distribute_devices(ctx)

        def stage():
            bench._discard_device_scratch(ctx)   # last pass's W inverses
            # dpotrf_L touches only the lower triangle
            bench.prestage(A, ctx, spd_diag=True, seed0=s0,
                           keep=lambda m, k: m >= k)
        setup_s, run_s = _run_passes(
            ctx, passes, t0, stage,
            lambda: potrf_taskpool(A, device="tpu"), A)

        gen = bench._tile_generator(A)
        lin = {t: i for i, t in enumerate(A.local_tiles())}

        def orig(m, k):
            return gen(float(s0 + lin[(m, k)]),
                       float(A.lm) if m == k else 0.0)

        accs = ctx.device_registry.accelerators
        t1 = time.perf_counter()
        bwd = backward_error(A, orig,
                             device=accs[0].jdev if len(accs) > 1 else None)
        check_s = time.perf_counter() - t1
        kept = {t: _to_host(_newest(A, *t)) for t in picked} \
            if keep_samples else {}
        devices = _device_report(ctx, A)
        ici = _ici_stats(ctx)
        _drop(ctx, A)
    del A
    gc.collect()
    out = {"phase": "potrf", "mb": mb, "nt": nt, "n": n,
           "storage": np.dtype(dtype).name, "distributed": distribute,
           "mca": POTRF_MCA, "setup_s": round(setup_s, 3),
           "run_s": [round(t, 3) for t in run_s],
           "check_s": round(check_s, 3), "backward_error": bwd,
           "devices": devices, "ici": ici}
    if not bwd <= POTRF_TOL:
        raise SmokeFailure(f"potrf: backward error {bwd:.3e} > "
                           f"{POTRF_TOL}: {out}")
    _require_healthy("potrf", devices, every_device=distribute)
    if keep_samples:
        out["_samples"] = kept
    return out


# ---------------------------------------------------------------------------
# geqrf
# ---------------------------------------------------------------------------

def run_geqrf(mb: int, nt: int, seed: int = 0, mp: bool = True,
              ib: int = 512, passes: int = 2, mca=None) -> dict:
    """Tiled QR with the inner-blocked (ib) panel engine on a Gaussian +
    identity matrix born on the device; the factor is held to
    R^T R = A^T A on a random probe (apps/qr_check).  ``mca`` goes on
    top of GEQRF_MCA; the phase is named after the panel path it took."""
    import jax.numpy as jnp
    from parsec_tpu.apps.qr import effective_ib, qr_taskpool
    from parsec_tpu.apps.qr_check import factorization_residual
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic

    dtype = _bf16() if mp else np.float32
    n = nt * mb
    A = TwoDimBlockCyclic(mb=mb, nb=mb, lm=n, ln=n, name="A", dtype=dtype)
    s0 = 2003 * seed
    mca = {**GEQRF_MCA, **(mca or {}), "qr_ib": ib}

    t0 = time.perf_counter()
    with _mca(**mca), Context(nb_cores=4) as ctx:
        ib_used = effective_ib(mb)
        chain = bool(int(params.get("device_fuse_panel", 1)))

        def stage():
            bench._discard_device_scratch(ctx)   # last pass's Q panels
            # Gaussian tiles + identity bump: full rank, and stacked
            # panels well-conditioned for Cholesky-QR (bench.py geqrf)
            bench.prestage(A, ctx, bump_all=1.0, rand_scale=0.05, seed0=s0)
        setup_s, run_s = _run_passes(
            ctx, passes, t0, stage, lambda: qr_taskpool(A, device="tpu"), A)

        gen = bench._tile_generator(A, 0.05)
        lin = {t: i for i, t in enumerate(A.local_tiles())}
        t1 = time.perf_counter()
        res = factorization_residual(
            A, lambda m, k: gen(float(s0 + lin[(m, k)]),
                                1.0).astype(jnp.float32))
        check_s = time.perf_counter() - t1
        devices = _device_report(ctx, A)
        _drop(ctx, A)
    del A
    gc.collect()
    out = {"phase": "geqrf" if chain else "geqrf_per_kernel",
           "mb": mb, "nt": nt, "n": n, "ib": ib_used,
           "storage": np.dtype(dtype).name, "mca": mca,
           "setup_s": round(setup_s, 3),
           "run_s": [round(t, 3) for t in run_s],
           "check_s": round(check_s, 3), "factorization_residual": res,
           "devices": devices}
    if ib_used != ib:
        raise SmokeFailure(f"geqrf: asked for ib={ib}, the panel engine "
                           f"ran ib={ib_used}")
    if not res <= GEQRF_TOL:
        raise SmokeFailure(f"geqrf: factorization residual {res:.3e} > "
                           f"{GEQRF_TOL}: {out}")
    _require_healthy("geqrf", devices, every_device=False)
    return out


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
        T = TwoDimBlockCyclic(mb=mb, nb=mb, lm=mb, ln=mb, dtype=_bf16())
        gen = bench._tile_generator(T, 1.0)
        sent = [jax.device_put(gen(float(31 * seed + i), 0.0), d.jdev)
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
                          distribute=dist, keep_samples=True)
            g = run_gemm(**gemm_size, seed=seed, passes=1, distribute=dist,
                         panel_bcast=True, keep_samples=True)
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
    return {"phase": "multichip",
            "potrf_backward_error": {"many": p4["backward_error"],
                                     "one": p1["backward_error"]},
            "potrf_tiles_rel_diff": _agree("potrf", p4, p1, POTRF_TOL),
            "gemm_rel_err_vs_jnp": {"many": g4["rel_err_vs_jnp"],
                                    "one": g1["rel_err_vs_jnp"]},
            "gemm_tiles_rel_diff": _agree("gemm", g4, g1, GEMM_TOL),
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
                geqrf = run_geqrf(mb=6144, nt=GEQRF_NT, seed=args.seed)
                emit(geqrf)
                if not geqrf["devices"][0]["stats"]["chained_launches"]:
                    raise SmokeFailure(
                        f"geqrf: chain fusion never engaged: "
                        f"{geqrf['devices'][0]['stats']}")
                emit(run_geqrf(mb=6144, nt=8, seed=args.seed,
                               mca=GEQRF_PER_KERNEL))
    except SmokeFailure as exc:
        log(f"chip_smoke: FAILED: {exc}")
        return 1
    log(f"chip_smoke: passed in {time.perf_counter() - t0:.0f}s, compile "
        f"cache now {cache_entries()} entries")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

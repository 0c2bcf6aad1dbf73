"""Smoke run of the system's two device paths on one TPU chip.

    python chip_smoke.py [--seed N]

One process, nothing caught: a failed phase ends the run with a traceback
and a nonzero exit.  JAX's first device must be a TPU; anywhere else the
script exits nonzero before any phase and prints no result.

* **dispatch** — a causally consistent mixed ARRIVAL/FINISH/EVICT stream
  over the 40-function catalogue (``trace.make_functions``, Azure-skewed
  popularity) on 1,600 workers, 64 chunks of 1,024 events, goes through
  ``sched_many_fused`` (the fused Pallas kernel) on the chip.  Assignments,
  warm flags and the final ``idle``/``conns`` must equal ``sched_many`` (the
  ``lax.scan``) on the chip and the host ``HikuScheduler`` with lowest-id
  ties.  One chunk at 100,000 workers, from the state a 20,000-event prefix
  leaves, is checked the same way.  Then the idle table that VMEM cannot
  hold: 8,192 functions (Azure-skewed) x 5,000 workers from a seeded start
  state, a seeded 2,000-event stream of all four kinds (two chunks) and a
  534-event one from the state it leaves, through ``sched_many_fused`` (the
  HBM form) against ``sched_many``, bit-exact.
* **served** — ``ServingEngine`` under the ``hiku`` scheduler, 2 workers,
  3 endpoints of the full-width ``mamba2_130m`` (seeds 0-2), 8 requests:
  cold starts and warm hits on both workers; every request for an endpoint
  must return the same tokens, and each generated token must be the best,
  or within ``MAX_GAP_STD`` of the best, under the same endpoint run on the
  host CPU with the chip's tokens fed back.

Earlier lines report what ran; the last line is the JSON result.  Host-clock
times below are readings on the chip machine, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs import get_config  # noqa: E402
from repro.core import HikuScheduler, JIQState, sched_many, sched_many_fused  # noqa: E402
from repro.core import jax_sched  # noqa: E402
from repro.core.jax_sched import ARRIVAL, EVICT, FINISH  # noqa: E402
from repro.core.trace import azure_like_weights, make_functions  # noqa: E402
from repro.serving import Endpoint, Instance, ServingEngine  # noqa: E402
from repro.utils.compile_cache import compile_count, enable_compile_cache  # noqa: E402

N_WORKERS, N_CHUNKS, WIDE_WORKERS = 1600, 64, 100_000
HBM_FUNCS, HBM_WORKERS, HBM_EVENTS = 8192, 5000, (2000, 534)
GEN_LEN = 4
# a served token may trail the CPU reference's best logit by this many
# standard deviations of the logits (matmul precision differs between the
# chip and the CPU); a token picked from wrong numbers lies about 4.5 below
MAX_GAP_STD = 0.1


class _LowestTie:
    """``random.Random`` stand-in: every tie goes to the lowest worker id,
    the tie the kernel and the scan break.  The least-connections fallback
    draws ``randrange(t)`` over its ``t`` tied ids in ascending order."""

    def randrange(self, n):
        return 0

    def choice(self, xs):
        return min(xs)


def make_stream(n_workers, n_events, seed, n_prefix=0):
    """Drive the host ``HikuScheduler`` through a seeded mixed stream.

    A FINISH closes a running (worker, function) pair; an EVICT removes an
    enqueued idle entry; ARRIVAL picks a function by catalogue popularity.
    The first ``n_prefix`` events only shape the starting state.  Returns
    the start state ``(idle, conns)``, the ``(n_events, 3)`` events, the
    host's assignments and warm flags, and its final ``(idle, conns)``."""
    funcs = make_functions()
    names = [f.name for f in funcs]
    p = np.array([f.weight for f in funcs])
    rng = np.random.default_rng(seed)
    host = HikuScheduler(n_workers, seed=0)
    host.rng = _LowestTie()
    running, enqueued = [], []  # (worker, func) pairs
    total = n_prefix + n_events
    events = np.empty((total, 3), np.int32)
    assign = np.full(total, -1, np.int32)
    warm = np.zeros(total, bool)
    kind_u = rng.random(total)
    arrival_f = rng.choice(len(funcs), size=total, p=p)

    def take(pairs):
        j = int(rng.integers(len(pairs)))
        pairs[j], pairs[-1] = pairs[-1], pairs[j]
        return pairs.pop()

    def live_entry():
        while enqueued:
            w, f = take(enqueued)
            if host.idle_counts.get(names[f], {}).get(w):
                return w, f
        return None

    def state():
        idle = np.zeros((len(funcs), n_workers), np.int32)
        for f, name in enumerate(names):
            for w, c in host.idle_counts.get(name, {}).items():
                idle[f, w] = c
        conns = np.array([host.conns[w] for w in range(n_workers)], np.int32)
        return idle, conns

    start = state() if n_prefix == 0 else None
    for i in range(total):
        if i == n_prefix and n_prefix:
            start = state()
        u = kind_u[i]
        entry = live_entry() if u >= 0.9 else None
        if entry is not None:
            w, f = entry
            host.on_evict(w, names[f])
            events[i] = (EVICT, f, w)
        elif 0.45 <= u < 0.9 and running:
            w, f = take(running)
            host.on_finish(w, names[f])
            enqueued.append((w, f))
            events[i] = (FINISH, f, w)
        else:
            f = int(arrival_f[i])
            warm[i] = host.queue_depth(names[f]) > 0
            w = host.schedule(names[f])
            running.append((w, f))
            events[i] = (ARRIVAL, f, -1)
            assign[i] = w
    s = slice(n_prefix, total)
    return start, events[s], assign[s], warm[s], state()


def _equal(name, got, want):
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = int(np.sum(got != want)) if got.shape == want.shape else "shape"
        raise AssertionError(f"{name}: {bad} entries differ")


def check_dispatch(n_workers, n_events, seed, n_prefix=0, interpret=False):
    """One dispatch check.  ``interpret=True`` runs the kernel in Pallas's
    interpreter (off the chip, at test widths)."""
    (idle0, conns0), ev, assign, warm, (idle1, conns1) = make_stream(
        n_workers, n_events, seed, n_prefix
    )
    kinds = np.bincount(ev[:, 0], minlength=3)
    state = JIQState(jnp.asarray(idle0), jnp.asarray(conns0))
    events = jnp.asarray(ev)
    fused_state, (f_w, f_warm) = sched_many_fused(state, events, interpret=interpret)
    scan_state, (s_w, s_warm) = sched_many(state, events)
    for ref, (r_w, r_warm, r_idle, r_conns) in {
        "scan": (s_w, s_warm, scan_state.idle, scan_state.conns),
        "host": (assign, warm, idle1, conns1),
    }.items():
        _equal(f"assign vs {ref}", f_w, r_w)
        _equal(f"warm vs {ref}", f_warm, r_warm)
        _equal(f"idle vs {ref}", fused_state.idle, r_idle)
        _equal(f"conns vs {ref}", fused_state.conns, r_conns)
    print(
        f"dispatch W={n_workers} F={idle0.shape[0]} events={n_events} "
        f"(arrival/finish/evict={kinds[0]}/{kinds[1]}/{kinds[2]}, "
        f"warm={int(warm.sum())}, start idle entries={int(idle0.sum())}): "
        "fused == scan == host",
        flush=True,
    )


def check_dispatch_hbm(seed):
    """The HBM form: a table past the VMEM cap, two calls with the state
    carried, against the scan over both streams."""
    rng = np.random.default_rng(seed)
    idle0 = rng.integers(0, 3, (HBM_FUNCS, HBM_WORKERS), dtype=np.int32)
    conns0 = rng.integers(0, 5, HBM_WORKERS, dtype=np.int32)
    p = azure_like_weights(HBM_FUNCS, 0, population=HBM_FUNCS)
    n = sum(HBM_EVENTS)
    kinds = rng.integers(0, 4, n)
    ev = np.stack([kinds, rng.choice(HBM_FUNCS, n, p=p),
                   np.where(kinds == ARRIVAL, -1, rng.integers(0, HBM_WORKERS, n))],
                  1).astype(np.int32)
    state = JIQState(jnp.asarray(idle0), jnp.asarray(conns0))
    laid_out = state.idle.format.layout.major_to_minor
    cut = HBM_EVENTS[0]
    mid, (w1, warm1) = sched_many_fused(state, ev[:cut])
    end, (w2, warm2) = sched_many_fused(mid, ev[cut:])
    scan_state, (s_w, s_warm) = sched_many(state, jnp.asarray(ev))
    _equal("hbm assign vs scan", np.concatenate([w1, w2]), s_w)
    _equal("hbm warm vs scan", np.concatenate([warm1, warm2]), s_warm)
    _equal("hbm idle vs scan", end.idle, scan_state.idle)
    _equal("hbm conns vs scan", end.conns, scan_state.conns)
    counts = np.bincount(kinds, minlength=4)
    print(
        f"dispatch hbm W={HBM_WORKERS} F={HBM_FUNCS} events={cut}+{n - cut} "
        f"(arrival/finish/evict/no-op={'/'.join(map(str, counts))}, "
        f"warm={int(np.asarray(s_warm).sum())}; table layout {laid_out} in, "
        f"{end.idle.format.layout.major_to_minor} out): fused == scan",
        flush=True,
    )


def dispatch_phase(seed):
    check_dispatch(N_WORKERS, N_CHUNKS * jax_sched.CHUNK, seed)
    check_dispatch(WIDE_WORKERS, jax_sched.CHUNK, seed + 1, n_prefix=20_000)
    check_dispatch_hbm(seed + 2)


def reference_gaps(ep, prompt, toks):
    """Teacher-force the chip's greedy tokens through the same endpoint
    built on the host CPU (its own init from the endpoint's seed, full f32
    on the CPU) and return, per generated token, how far that token's CPU
    logit lies below the CPU's best, in units of the logits' standard
    deviation; 0 where the chip and the CPU pick the same token.  Decoding
    follows ``Instance.generate``: a fresh cache, decode from the prompt
    length."""
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        inst = Instance(ep)
        toks = jnp.asarray(toks)
        cache = inst.model.init_cache(1, ep.max_cache_len, dtype=jnp.float32,
                                      memory_t=8)
        prompt = jnp.asarray(np.asarray(prompt))
        _, logits = inst.prefill_fn(inst.params, {"tokens": prompt})
        steps = [logits]
        idx = jnp.int32(min(prompt.shape[1], ep.max_cache_len - GEN_LEN - 1))
        for i in range(GEN_LEN - 1):
            logits, cache = inst.decode_fn(inst.params, toks[:, i : i + 1], cache,
                                           idx + i)
            steps.append(logits)
    gaps = []
    for i, lg in enumerate(steps):
        lg = np.asarray(lg[0], np.float64)
        gaps.append(float((lg.max() - lg[int(toks[0, i])]) / lg.std()))
    return gaps


def served_phase(seed):
    cfg = get_config("mamba2_130m")
    eps = [Endpoint(f"mamba2-{i}", cfg, seed=i) for i in range(3)]
    eng = ServingEngine(eps, n_workers=2, scheduler="hiku", seed=seed)
    rng = np.random.default_rng(seed)
    prompts = {e.name: jnp.asarray(rng.integers(0, cfg.vocab, (1, 8)), jnp.int32)
               for e in eps}
    plan = [e.name for e in eps] * 2 + [eps[0].name, eps[1].name]
    first = {}
    seen = set()
    for name in plan:
        r = eng.submit(name, tokens=prompts[name], gen_len=GEN_LEN)
        toks = np.asarray(r.tokens)
        if toks.shape != (1, GEN_LEN) or toks.min() < 0 or toks.max() >= cfg.vocab:
            raise AssertionError(f"{name}: bad tokens {toks}")
        if name in first:
            _equal(f"{name} tokens vs its first request", toks, first[name])
        else:
            first[name] = toks
        seen.add((r.worker, r.cold))
        print(
            f"served {name} -> w{r.worker} {'cold' if r.cold else 'warm'} "
            f"init_ms={r.init_ms} exec_ms={r.exec_ms} tokens={toks[0].tolist()}",
            flush=True,
        )
    want = {(w, c) for w in range(2) for c in (True, False)}
    if not want <= seen:
        raise AssertionError(f"no cold start and warm hit on both workers: {seen}")
    stats = jax.devices()[0].memory_stats() or {}
    print(
        f"served {cfg.name} ({cfg.n_params()} params): warm tokens == cold "
        f"tokens; bytes_in_use={stats.get('bytes_in_use')} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
        flush=True,
    )
    for ep in eps:
        gaps = reference_gaps(ep, prompts[ep.name], first[ep.name])
        print(f"served {ep.name} vs host-CPU reference: gap/std per token {gaps}",
              flush=True)
        if max(gaps) > MAX_GAP_STD:
            raise AssertionError(
                f"{ep.name}: a chip token lies {max(gaps)} std below the "
                f"CPU reference's best logit (limit {MAX_GAP_STD})"
            )


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform!r}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    count = compile_count()
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir}", flush=True)
    for name, phase in (("dispatch", dispatch_phase), ("served", served_phase)):
        t0 = time.perf_counter()
        phase(args.seed)
        print(f"phase {name}: wall_s={time.perf_counter() - t0} "
              f"compiles={count.compiles} compile_s={count.seconds} "
              f"cache_hits={count.cache_hits}",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

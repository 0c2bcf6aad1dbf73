"""Worker host: the function-instance lifecycle (Figure 2) on real JAX models.

A worker owns an HBM memory pool and a sandbox table of *instances* — a
materialized param set + jitted prefill/decode executables for one endpoint
("function type").  Cold start = param materialization + XLA compile (+ cache
allocation); warm start = reuse of a resident idle instance.  The evictor
implements keep-alive timeouts and LRU force-eviction under memory pressure,
emitting the scheduler notifications of Section IV-A.

This is the *real-compute* control plane (Table-I-style measurements run on
it).  Timing studies at cluster scale use core/simulator.py — recorded in
DESIGN.md §2.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models import build_model, unzip


@dataclasses.dataclass
class Endpoint:
    """A deployable function type: model config + weight seed."""

    name: str
    cfg: object  # ModelConfig
    seed: int = 0
    max_cache_len: int = 128

    def est_bytes(self) -> int:
        p = self.cfg.n_params() * 4  # f32 on CPU host
        return int(p * 1.2) + 64 * self.max_cache_len * 1024


class Instance:
    """One warm sandbox: params + compiled serve executables."""

    __slots__ = ("endpoint", "model", "params", "decode_fn", "prefill_fn", "last_used", "busy")

    def __init__(self, endpoint: Endpoint):
        self.endpoint = endpoint
        model = build_model(endpoint.cfg, param_dtype=jnp.float32, remat=False)
        self.model = model
        params, _ = unzip(model.init(jax.random.key(endpoint.seed), max_seq=endpoint.max_cache_len))
        self.params = jax.tree.map(lambda a: jax.block_until_ready(a), params)
        self.prefill_fn = jax.jit(model.prefill)
        self.decode_fn = jax.jit(model.decode_step)
        self.last_used = time.monotonic()
        self.busy = False

    def generate(self, tokens: jnp.ndarray, gen_len: int = 4) -> jnp.ndarray:
        """Prefill + a few decode steps (the 'function execution')."""
        model, ep = self.model, self.endpoint
        B, S = tokens.shape
        cache = model.init_cache(B, ep.max_cache_len, dtype=jnp.float32, memory_t=8)
        if ep.cfg.enc_dec:
            frames = jnp.zeros((B, S, ep.cfg.d_model), jnp.float32)
            batch = {"frames": frames, "tokens": tokens}
        else:
            batch = {"tokens": tokens}
        _, last_logits = self.prefill_fn(self.params, batch)
        out = [jnp.argmax(last_logits, -1)]
        idx = jnp.int32(min(S, ep.max_cache_len - gen_len - 1))
        for i in range(gen_len - 1):
            logits, cache = self.decode_fn(self.params, out[-1][:, None], cache, idx + i)
            out.append(jnp.argmax(logits, -1))
        return jax.block_until_ready(jnp.stack(out, 1))


@dataclasses.dataclass
class ExecutionRecord:
    func: str
    worker: int
    cold: bool
    init_ms: float
    exec_ms: float
    tokens: jax.Array  # (B, gen_len) generated token ids


class WorkerHost:
    def __init__(self, wid: int, mem_pool_bytes: int = 2 * 2**30, keep_alive_s: float = 60.0):
        self.wid = wid
        self.pool = mem_pool_bytes
        self.keep_alive_s = keep_alive_s
        self.idle: Dict[str, List[Instance]] = {}
        self.used_bytes = 0
        self.on_evict: Optional[Callable[[int, str], None]] = None

    # ------------------------------------------------------------- memory
    def _evict_lru(self) -> bool:
        lru_key, lru_i, lru_t = None, -1, float("inf")
        for name, lst in self.idle.items():
            for i, inst in enumerate(lst):
                if inst.last_used < lru_t:
                    lru_key, lru_i, lru_t = name, i, inst.last_used
        if lru_key is None:
            return False
        inst = self.idle[lru_key].pop(lru_i)
        if not self.idle[lru_key]:
            del self.idle[lru_key]
        self.used_bytes -= inst.endpoint.est_bytes()
        if self.on_evict:
            self.on_evict(self.wid, lru_key)
        return True

    def sweep(self) -> None:
        now = time.monotonic()
        for name in list(self.idle):
            keep = []
            for inst in self.idle[name]:
                if now - inst.last_used > self.keep_alive_s:
                    self.used_bytes -= inst.endpoint.est_bytes()
                    if self.on_evict:
                        self.on_evict(self.wid, name)
                else:
                    keep.append(inst)
            if keep:
                self.idle[name] = keep
            else:
                del self.idle[name]

    # ------------------------------------------------------------ execute
    def execute(self, ep: Endpoint, tokens: jnp.ndarray, gen_len: int = 4) -> ExecutionRecord:
        cold = not self.idle.get(ep.name)
        t0 = time.perf_counter()
        if cold:
            need = ep.est_bytes()
            while self.used_bytes + need > self.pool and self._evict_lru():
                pass
            inst = Instance(ep)  # materialize + compile == cold start
            self.used_bytes += need
        else:
            inst = self.idle[ep.name].pop()
        t1 = time.perf_counter()
        out = inst.generate(tokens, gen_len)
        t2 = time.perf_counter()
        inst.last_used = time.monotonic()
        self.idle.setdefault(ep.name, []).append(inst)
        return ExecutionRecord(
            func=ep.name, worker=self.wid, cold=cold,
            init_ms=(t1 - t0) * 1e3 if cold else 0.0,
            exec_ms=(t2 - t1) * 1e3,
            tokens=out,
        )

"""Serving engine: chunked prefill + scanned decode with placed KV caches.

The KV cache is a first-class *placeable object*: the engine sizes it
from the model config, asks the MEMSCOPE :class:`PlacementAdvisor` which
pool it belongs in under the expected contention (HBM normally; host DRAM
when HBM capacity is the binding constraint — the long-context regime),
and materialises it through the chosen upool.  This is the paper's
Fig. 14 loop (characterize -> place -> run) applied to an inference
server.  A hybrid model holds two kinds of cache side by side — the KV
cache of its attention layers and the recurrent state of its state-space
layers — and the engine advises and places them as two objects, each at
its own read/write mix (:func:`choose_cache_pools`).

The loop also closes *online*: pass a
:class:`repro.serve.monitor.ServeMonitor` and the engine times every
decode step on a monitored python loop — the watchdog detects contention
drift against the surface's expectation, a resilient background probe
sweep refreshes the drifted cells under ``qualifier="online"``, and the
migration guard moves the live caches (with hysteresis + rollback) when
the refreshed surface flips the advisor's decision.  Every drift event,
probe sweep, migration and rollback lands in :class:`GenerateResult`.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ServeConfig
from repro.core import spans
from repro.models import lm
from repro.parallel.sharding import ShardingRules
from repro.train.step import make_constrain

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Cache sizing / placement
# ---------------------------------------------------------------------------


CACHE_KINDS = ("kv", "state")


def cache_kind(group: Params) -> str:
    """``kv`` for an attention layer's cache, ``state`` for a state-space
    layer's (its recurrent state and conv tail)."""
    return "kv" if "k" in group else "state"


def _groups(caches: Params):
    """Every layer's cache group of a cache pytree, as (top, name,
    group): ``caches[top][name]`` is one layer position's cache."""
    for top, grp in caches.items():
        for name, group in grp.items():
            yield top, name, group


def cache_bytes(cfg: ModelConfig, batch: int, max_len: int,
                kv_dtype=jnp.bfloat16, kind: Optional[str] = None) -> int:
    """Bytes of the decode caches, or of one ``kind`` of them."""
    import math
    struct = lm.cache_struct(cfg, batch, max_len, kv_dtype)
    return sum(int(s.dtype.itemsize) * math.prod(s.shape)
               for _t, _n, group in _groups(struct)
               if kind in (None, cache_kind(group))
               for s in jax.tree.leaves(group))


def decode_rw_mix(batch: int, max_len: int) -> float:
    """Read share of the decode step's KV traffic (the ``rw_ratio``
    surface coordinate).  Each generated token reads the whole cache
    prefix — ``max_len`` positions per sequence — and writes exactly
    one new slot, so the mix approaches pure-read as contexts grow."""
    reads = float(max(1, max_len))
    return reads / (reads + 1.0)


def pool_capacities(advisor, *, pool_mgr=None,
                    hbm_free_bytes: Optional[int] = None,
                    ) -> Optional[Dict[str, int]]:
    """Candidate-pool capacities for the KV placement solve.

    Live accounting first: a pool manager knows what is *actually*
    free (``pool.available`` = capacity - allocated), so a half-full
    HBM constrains the solve instead of its nameplate size.  Without a
    manager the advisor's own platform capacities apply (the advise()
    default), overridden per-pool by ``hbm_free_bytes`` — no pool's
    capacity is ever invented (the seed hard-coded ``host: 256 GiB``).
    """
    caps: Dict[str, int] = {}
    if pool_mgr is not None:
        from repro.core.pools import PoolError
        for p in advisor.pools:
            try:
                caps[p] = pool_mgr.pool(p).available
            except PoolError:
                continue            # the platform has no such pool
    elif hbm_free_bytes is not None:
        caps = {p: advisor.platform.memories[p].size_bytes
                for p in advisor.pools if p in advisor.platform.memories}
    if hbm_free_bytes is not None and ("hbm" in caps or not caps):
        caps["hbm"] = hbm_free_bytes
    return caps or None


def choose_kv_pool(cfg: ModelConfig, batch: int, max_len: int, *,
                   advisor=None, scfg: Optional[ServeConfig] = None,
                   pool_mgr=None,
                   hbm_free_bytes: Optional[int] = None,
                   rw_mix: Optional[float] = None,
                   inject_rate: Optional[float] = None) -> str:
    scfg = scfg or ServeConfig()
    if scfg.kv_placement != "auto":
        return scfg.kv_placement
    if advisor is None:
        return "hbm"
    from repro.core.placement import ContentionSpec, kv_cache_object
    nbytes = cache_bytes(cfg, batch, max_len)
    obj = kv_cache_object("kv", nbytes, bytes_read_per_token=float(nbytes))
    caps = pool_capacities(advisor, pool_mgr=pool_mgr,
                           hbm_free_bytes=hbm_free_bytes)
    # advise at the engine's observed decode traffic coordinates: the
    # surface interpolates its rw_ratio axis at the cache's actual
    # read/write mix (and its inject_rate axis at the engine's observed
    # decode duty cycle) instead of a letter-keyed worst case
    if rw_mix is None:
        rw_mix = decode_rw_mix(batch, max_len)
    plan = advisor.advise(
        [obj], ContentionSpec(0, rw_ratio=rw_mix,
                              inject_rate=inject_rate),
        capacities=caps)
    return plan.pool_of("kv")


def choose_cache_pools(cfg: ModelConfig, batch: int, max_len: int, *,
                       advisor=None, scfg: Optional[ServeConfig] = None,
                       pool_mgr=None,
                       hbm_free_bytes: Optional[int] = None,
                       rw_mix: Optional[float] = None,
                       inject_rate: Optional[float] = None
                       ) -> Dict[str, str]:
    """The pool of each kind of cache the model holds (``kv``,
    ``state``).  A model with one kind is advised as one object, exactly
    as :func:`choose_kv_pool` advises it.  A hybrid model's two kinds are
    advised together, as two objects that compete for the same
    capacities: the KV cache, which grows with the context and of which
    each decode step reads nearly all (:func:`decode_rw_mix`), and the
    recurrent state, fixed in size, which each step reads and rewrites
    whole (read share 0.5)."""
    sizes = {k: cache_bytes(cfg, batch, max_len, kind=k)
             for k in CACHE_KINDS}
    kinds = [k for k in CACHE_KINDS if sizes[k]]
    scfg = scfg or ServeConfig()
    if len(kinds) == 1 or advisor is None or scfg.kv_placement != "auto":
        pool = choose_kv_pool(cfg, batch, max_len, advisor=advisor,
                              scfg=scfg, pool_mgr=pool_mgr,
                              hbm_free_bytes=hbm_free_bytes, rw_mix=rw_mix,
                              inject_rate=inject_rate)
        return dict.fromkeys(kinds, pool)
    from repro.core.placement import (ContentionSpec, kv_cache_object,
                                      recurrent_state_object)
    if rw_mix is None:
        rw_mix = decode_rw_mix(batch, max_len)
    objs = [kv_cache_object("kv", sizes["kv"],
                            bytes_read_per_token=float(sizes["kv"]),
                            rw_ratio=rw_mix),
            recurrent_state_object("state", sizes["state"])]
    plan = advisor.advise(
        objs, ContentionSpec(0, rw_ratio=rw_mix, inject_rate=inject_rate),
        capacities=pool_capacities(advisor, pool_mgr=pool_mgr,
                                   hbm_free_bytes=hbm_free_bytes))
    return {o.name: plan.pool_of(o.name) for o in objs}


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ModelConfig, rules: ShardingRules, *,
                      max_len: int, q_chunk: int = 256):
    cst = make_constrain(rules)

    def prefill(params: Params, tokens, frontend=None):
        hidden, caches, _ = lm.forward(
            params, tokens, cfg=cfg, mode="prefill", frontend=frontend,
            constrain=cst, max_len=max_len, q_chunk=q_chunk)
        logits = lm.unembed_logits(params, hidden[:, -1:], cfg)
        return caches, logits[:, 0]

    return prefill


def make_decode_step(cfg: ModelConfig, rules: ShardingRules):
    cst = make_constrain(rules)

    def decode(params: Params, caches: Params, token, write_pos,
               frontend=None):
        """token: (B, 1) int32; write_pos: scalar int32 (absolute)."""
        hidden, caches, _ = lm.forward(
            params, token, cfg=cfg, mode="decode", caches=caches,
            write_pos=write_pos, frontend=frontend, constrain=cst)
        logits = lm.unembed_logits(params, hidden, cfg)
        return caches, logits[:, 0]

    return decode


def sample_token(logits, key, temperature: float = 0.0):
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits / temperature, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


@dataclass
class GenerateResult:
    tokens: Any                 # (B, T)
    steps: int
    kv_pool: str                # the pool the KV caches ENDED in (the
                                # state's, for a model with no KV cache)
    # (B, V) f32 logits the last emitted token was sampled from
    last_logits: Any = None
    # online-loop provenance (monitored decode only; empty otherwise)
    drift_events: List[Any] = field(default_factory=list)
    migrations: List[Any] = field(default_factory=list)
    probe_sweeps: int = 0


class ServeEngine:
    """Batched prefill+decode over a placed KV cache.

    ``monitor`` (a :class:`repro.serve.monitor.ServeMonitor`) switches
    ``generate`` onto the monitored decode loop: per-step wall timing
    feeds the contention watchdog and the engine applies the monitor's
    migrate/rollback actions to the live caches between steps.  The
    unmonitored path keeps the fused ``lax.scan`` decode loop."""

    def __init__(self, cfg: ModelConfig, params: Params,
                 rules: ShardingRules, scfg: Optional[ServeConfig] = None,
                 advisor=None, pool_mgr=None, monitor=None):
        self.cfg = cfg
        self.params = params
        self.rules = rules
        self.scfg = scfg or ServeConfig()
        self.advisor = advisor
        self.pool_mgr = pool_mgr
        self.monitor = monitor
        self._decode = jax.jit(make_decode_step(cfg, rules),
                               donate_argnums=(1,))
        # jitted prefill per max_len: repeated generate calls at the
        # same shape reuse ONE trace (the seed re-jitted every call)
        self._prefill_cache: Dict[int, Callable] = {}
        # jitted decode loop per (steps, temperature): an eager lax.scan
        # with a fresh body was traced again, and its program fetched
        # from the compile cache, on every call
        self._loop_cache: Dict[Tuple[int, float], Callable] = {}
        # observed decode duty cycle (EWMA across generate calls): the
        # inject_rate coordinate the engine feeds back into placement
        self._duty: Optional[float] = None

    # -- jit caches ----------------------------------------------------------
    def _prefill(self, max_len: int) -> Callable:
        fn = self._prefill_cache.get(max_len)
        if fn is None:
            fn = jax.jit(make_prefill_step(self.cfg, self.rules,
                                           max_len=max_len))
            self._prefill_cache[max_len] = fn
        return fn

    def _decode_loop(self, n_steps: int, temperature: float) -> Callable:
        """``n_steps`` decode steps in one program.  It is named ``scan``
        so that, jitted, it compiles as the module ``jit_scan``, the name
        an eager ``lax.scan`` of the steps compiled as."""
        fn = self._loop_cache.get((n_steps, temperature))
        if fn is None:
            decode = self._decode

            def scan(params, caches, tok, logits, key, start):
                def body(carry, i):
                    caches, tok, _logits, key = carry
                    key, sub = jax.random.split(key)
                    caches, logits = decode(params, caches, tok, start + i)
                    nxt = sample_token(logits, sub, temperature)[:, None]
                    return (caches, nxt, logits, key), tok[:, 0]

                return jax.lax.scan(body, (caches, tok, logits, key),
                                    jnp.arange(n_steps, dtype=jnp.int32))

            fn = jax.jit(scan, donate_argnums=(1,))
            self._loop_cache[(n_steps, temperature)] = fn
        return fn

    # -- placement -----------------------------------------------------------
    def _place_caches(self, caches: Params, pool_name: str) -> Params:
        """Materialise the cache pytree in ``pool_name`` via its upool.
        With a pool manager every pool goes through ``upool.place`` —
        including "hbm", so a rollback moves host-placed arrays BACK to
        device memory instead of silently leaving them put.  A pool the
        platform cannot back raises (``PoolError``)."""
        if self.pool_mgr is None:
            return caches
        return self.pool_mgr.upool(pool_name).place(caches)

    def _place_by_kind(self, caches: Params, pools: Dict[str, str]
                       ) -> Params:
        """Each layer's cache in the pool ``pools`` gives its kind; one
        placement of the whole pytree where every kind shares a pool."""
        if len(set(pools.values())) <= 1:
            return self._place_caches(caches, next(iter(pools.values())))
        out: Params = {top: {} for top in caches}
        for top, name, group in _groups(caches):
            out[top][name] = self._place_caches(group,
                                                pools[cache_kind(group)])
        return out

    def duty_cycle(self) -> Optional[float]:
        return self._duty

    def _observe_duty(self, busy_s: float, wall_s: float) -> None:
        if wall_s <= 0.0:
            return
        d = min(1.0, busy_s / wall_s)
        self._duty = d if self._duty is None else 0.2 * d + 0.8 * self._duty

    # -- generation ----------------------------------------------------------
    def generate(self, tokens, *, max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 frontend=None,
                 on_step: Optional[Callable[[int, str], None]] = None,
                 ) -> GenerateResult:
        cfg, rules = self.cfg, self.rules
        b, s = tokens.shape
        max_len = s + max_new_tokens
        rw_mix = decode_rw_mix(b, max_len)

        caches, logits = self._prefill(max_len)(self.params, tokens,
                                                frontend)
        # advised while the device runs the prefill; placing waits for it
        with spans.span("place", **{
                f"{k}_bytes": cache_bytes(cfg, b, max_len, kind=k)
                for k in CACHE_KINDS}) as span:
            pools = choose_cache_pools(
                cfg, b, max_len, advisor=self.advisor, scfg=self.scfg,
                pool_mgr=self.pool_mgr, rw_mix=rw_mix,
                inject_rate=self._duty)
            span.set_metadata(**{f"{k}_pool": pools.get(k, "")
                                 for k in CACHE_KINDS})
            caches = self._place_by_kind(caches, pools)
        kv_pool = pools.get("kv", pools.get("state"))

        key = jax.random.PRNGKey(seed)
        tok = sample_token(logits, key, temperature)[:, None]

        if self.monitor is None and on_step is None:
            return self._generate_scan(caches, tok, logits, key, s,
                                       max_new_tokens, temperature,
                                       kv_pool)
        return self._generate_monitored(caches, tok, logits, key, s, b,
                                        max_len, max_new_tokens,
                                        temperature, kv_pool, rw_mix,
                                        on_step)

    def _generate_scan(self, caches, tok, logits, key, s: int,
                       max_new_tokens: int, temperature: float,
                       kv_pool: str) -> GenerateResult:
        # prefill already sampled token 0; decode the remaining N-1
        (caches, last, logits, _), toks = self._decode_loop(
            max_new_tokens - 1, temperature)(
            self.params, caches, tok, logits, key, jnp.int32(s))
        out = jnp.concatenate(
            [jnp.moveaxis(toks, 0, 1), last], axis=1) \
            if max_new_tokens > 1 else last
        return GenerateResult(out, max_new_tokens, kv_pool,
                              last_logits=logits)

    def _generate_monitored(self, caches, tok, logits, key, s: int,
                            b: int, max_len: int, max_new_tokens: int,
                            temperature: float, kv_pool: str,
                            rw_mix: float, on_step) -> GenerateResult:
        """The python decode loop: token-identical to the scan path
        (same split order, same pre-update emission), with each step
        wall-timed for the watchdog.  ``on_step(abs_step, pool)`` runs
        INSIDE the timed window — it stands in for the external
        contention the step experiences (benchmarks inject load
        there)."""
        mon = self.monitor
        d0 = m0 = r0 = 0
        if mon is not None:
            mon.bind(kv_bytes=cache_bytes(self.cfg, b, max_len),
                     rw_mix=rw_mix, pool=kv_pool,
                     inject_rate=self._duty,
                     capacities=pool_capacities(self.advisor,
                                                pool_mgr=self.pool_mgr)
                     if self.advisor is not None else None)
            kv_pool = mon.pool or kv_pool
            d0 = len(mon.drift_events)
            m0 = len(mon.migrations)
            r0 = len(mon.refreshes)

        emitted: List[Any] = []
        busy_s = 0.0
        t_loop = time.perf_counter()
        for i in range(max_new_tokens - 1):
            key, sub = jax.random.split(key)
            t0 = time.perf_counter()
            if on_step is not None:
                on_step(s + i, kv_pool)
            caches, logits = self._decode(self.params, caches, tok,
                                          s + i)
            logits.block_until_ready()
            wall_s = time.perf_counter() - t0
            busy_s += wall_s
            nxt = sample_token(logits, sub, temperature)[:, None]
            emitted.append(tok[:, 0])
            tok = nxt
            if mon is not None:
                action = mon.on_step(wall_s * 1e9)
                if action is not None:
                    caches = self._place_caches(caches, action.to_pool)
                    kv_pool = action.to_pool
        self._observe_duty(busy_s, time.perf_counter() - t_loop)

        out = jnp.concatenate(
            [jnp.stack(emitted, axis=1), tok], axis=1) \
            if max_new_tokens > 1 else tok
        result = GenerateResult(out, max_new_tokens, kv_pool,
                                last_logits=logits)
        if mon is not None:
            result.drift_events = list(mon.drift_events[d0:])
            result.migrations = list(mon.migrations[m0:])
            result.probe_sweeps = len(mon.refreshes) - r0
        return result

"""Genetic algorithm for optimal cut-point selection — paper §4.3 + App. D.

Minimizes `huscf_iteration_latency` over the joint per-client cut vector.
Implements the paper's exact operators:
  * tournament selection (size 5)
  * uniform crossover and two-point crossover, alternated 50/50,
    applied with probability `crossover_rate`
  * per-gene mutation with probability `mutation_rate`
  * elitism (top 2 carried over)
  * profile-based reduction (appendix D): one gene per *device profile*,
    upsampled to all clients for fitness evaluation.

Two execution paths:
  * ``GAConfig.fused=True`` (default): device-resident search. Fitness
    is the vectorized Eq. 3-10 model (``core.latency_jax``) over the
    whole ``[P, n_genes]`` population at once, and each generation
    (tournament gathers + argmax, 50/50 uniform/two-point crossover,
    per-gene mutation, ``top_k`` elitism) is one step of an in-graph
    ``lax.while_loop`` driven by a JAX PRNG key chain, with the
    early-stop patience as the loop exit. ``CutSearcher`` holds the
    staged tables + jitted program so *re*-optimization (churn,
    fluctuating bandwidth) costs one dispatch per round and runs under
    ``jax.transfer_guard("disallow_explicit")``.
  * ``GAConfig.fused=False``: the host numpy loop — one scalar fitness
    call per individual per generation — kept as the correctness /
    solution-quality oracle.

Bookkeeping convention (both paths): ``history[g]`` is generation g's
best latency with g=0 the *initial* population, so ``history`` has
``generations_run + 1`` entries; ``convergence_gen`` is the generation
whose population first contained the final best individual, and 0
means the initial population already did (the early-stop patience
counts generations since ``convergence_gen``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.core.latency import (Cut, DeviceProfile, PAPER_SERVER,
                                all_cut_options, huscf_iteration_latency)
from repro.core.latency_jax import (LatencyTables, build_latency_tables,
                                    population_latency)


@dataclasses.dataclass
class GAConfig:
    population_size: int = 1000
    generations: int = 60
    crossover_rate: float = 0.7
    mutation_rate: float = 0.01
    tournament_size: int = 5
    elitism: int = 2
    profile_based: bool = True
    seed: int = 0
    early_stop_patience: int = 15
    fused: bool = True           # device-resident GA; False = host numpy
    #                              oracle (identical operators, scalar
    #                              fitness per individual)


@dataclasses.dataclass
class GAResult:
    cuts: List[Cut]            # per client
    latency: float
    generations_run: int
    convergence_gen: int       # generation that first held the final best
    #                            (0 = already in the initial population)
    history: List[float]       # per-generation best, history[0] = gen 0


def _profile_reduction(devices: Sequence[DeviceProfile],
                       profile_based: bool
                       ) -> Tuple[Optional[np.ndarray], int]:
    """Appendix D: collapse clients with identical profiles to one gene.
    Returns (profile_of [K] or None, n_genes)."""
    if not profile_based:
        return None, len(devices)
    names = [d.name for d in devices]
    uniq = sorted(set(names))
    profile_idx = {nm: i for i, nm in enumerate(uniq)}
    return np.array([profile_idx[nm] for nm in names]), len(uniq)


def _upsample_cuts(ind: np.ndarray, profile_of: Optional[np.ndarray],
                   n_clients: int, options: List[Cut]) -> List[Cut]:
    if profile_of is not None:
        return [options[ind[profile_of[k]]] for k in range(n_clients)]
    return [options[g] for g in ind]


# ---------------------------------------------------------------------------
# fused device-resident search
# ---------------------------------------------------------------------------

class SearchOut(NamedTuple):
    """Device-array result of one fused GA run (read back at will)."""
    best_ind: jnp.ndarray        # [n_genes] int32 option indices
    best_latency: jnp.ndarray    # f32 scalar
    convergence_gen: jnp.ndarray  # int32
    generations_run: jnp.ndarray  # int32
    history: jnp.ndarray         # [generations+1] f32, nan-padded tail


@functools.lru_cache(maxsize=64)
def _get_search_fn(pop_size: int, n_genes: int, n_opt: int,
                   generations: int, crossover_rate: float,
                   mutation_rate: float, tournament_size: int,
                   elitism: int, patience: int,
                   with_counts: bool) -> Callable:
    """Jitted ``(key, LatencyTables[, counts]) -> SearchOut``, fully
    in-graph: one fused GA generation per while_loop step, the
    early-stop patience as the in-graph exit condition (like PR 4's
    Lloyd iteration). Cached on the static GA shape so every device
    population with the same (pop, genes) reuses one compiled program
    — tables/counts arrive as arguments, not baked constants."""
    spans.count("programs_built.ga")
    n_elite = max(0, min(elitism, pop_size - 1))
    n_child = pop_size - n_elite
    n_pairs = (n_child + 1) // 2
    gene_idx = np.arange(n_genes)[None, :]

    def eval_pop(tables: LatencyTables, counts, pop: jnp.ndarray
                 ) -> jnp.ndarray:
        return -population_latency(tables, pop, counts)

    def generation(tables, counts, carry):
        key, pop, fits, best_ind, best_fit, conv, stall, gen, hist = carry
        keys = jax.random.split(key, 8)
        # elitism: top individuals carried over unmodified
        _, elite_rows = jax.lax.top_k(fits, n_elite)
        elite = pop[elite_rows]
        # tournament selection: random index gathers + argmax, two
        # independent parents per pair
        t_idx = jax.random.randint(keys[1], (2, n_pairs, tournament_size),
                                   0, pop_size)
        win = jnp.take_along_axis(
            t_idx, jnp.argmax(fits[t_idx], axis=-1)[..., None],
            axis=-1)[..., 0]
        p1, p2 = pop[win[0]], pop[win[1]]              # [n_pairs, G]
        # 50/50 uniform / two-point crossover, applied with
        # probability crossover_rate per pair (a gene-swap mask either
        # way, so both children come from one jnp.where pair)
        do_cross = jax.random.uniform(keys[2], (n_pairs, 1)) < crossover_rate
        use_uniform = jax.random.uniform(keys[3], (n_pairs, 1)) < 0.5
        umask = jax.random.uniform(keys[4], (n_pairs, n_genes)) < 0.5
        pts = jnp.sort(jax.random.randint(keys[5], (n_pairs, 2), 0, n_genes),
                       axis=1)
        tmask = (gene_idx >= pts[:, :1]) & (gene_idx <= pts[:, 1:])
        swap = do_cross & jnp.where(use_uniform, umask, tmask)
        children = jnp.concatenate([jnp.where(swap, p2, p1),
                                    jnp.where(swap, p1, p2)], 0)[:n_child]
        # per-gene mutation
        mmask = jax.random.uniform(keys[6], (n_child, n_genes)) < mutation_rate
        mvals = jax.random.randint(keys[7], (n_child, n_genes), 0, n_opt)
        children = jnp.where(mmask, mvals, children)
        pop = jnp.concatenate([elite, children], 0)
        fits = eval_pop(tables, counts, pop)
        gen = gen + 1
        gen_best = jnp.max(fits)
        improved = gen_best > best_fit + 1e-12
        best_fit = jnp.where(improved, gen_best, best_fit)
        best_ind = jnp.where(improved, pop[jnp.argmax(fits)], best_ind)
        conv = jnp.where(improved, gen, conv)
        stall = jnp.where(improved, jnp.int32(0), stall + 1)
        hist = hist.at[gen].set(-gen_best)
        return (keys[0], pop, fits, best_ind, best_fit, conv, stall, gen,
                hist)

    @jax.named_scope(spans.SCOPE_GA)
    def search(key, tables: LatencyTables, counts=None) -> SearchOut:
        k_init, k_loop = jax.random.split(key)
        pop = jax.random.randint(k_init, (pop_size, n_genes), 0, n_opt,
                                 jnp.int32)
        fits = eval_pop(tables, counts, pop)
        best = jnp.argmax(fits)
        hist = jnp.full((generations + 1,), jnp.nan, jnp.float32)
        hist = hist.at[0].set(-fits[best])
        carry = (k_loop, pop, fits, pop[best], fits[best], jnp.int32(0),
                 jnp.int32(0), jnp.int32(0), hist)

        def cond(c):
            stall, gen = c[6], c[7]
            return (gen < generations) & (stall < patience)

        carry = jax.lax.while_loop(
            cond, functools.partial(generation, tables, counts), carry)
        _, _, _, best_ind, best_fit, conv, _, gen, hist = carry
        return SearchOut(best_ind, -best_fit, conv, gen, hist)

    if with_counts:
        return jax.jit(search)
    return jax.jit(lambda key, tables: search(key, tables, None))


class CutSearcher:
    """Staged, jitted GA cut search for one fixed device population.

    Build once (host-side table construction + trace), then ``run(key)``
    is a single dispatch with zero host<->device transfers — cheap
    enough to call every federation round. The trainer caches one
    searcher per (devices, server, batch, config) and rebuilds only on
    churn / profile change.
    """

    def __init__(self, devices: Sequence[DeviceProfile],
                 server: DeviceProfile = PAPER_SERVER, *,
                 batch: int = 64, config: GAConfig = None,
                 options: Optional[List[Cut]] = None):
        self.config = config = config or GAConfig()
        self.options = all_cut_options() if options is None else options
        self.n_clients = len(devices)
        profile_of, n_genes = _profile_reduction(devices,
                                                 config.profile_based)
        self.profile_of = profile_of
        self.n_genes = n_genes
        if profile_of is not None:
            # appendix D taken all the way: fitness itself collapses to
            # the unique profiles. Tables carry one row per profile and
            # a client-count vector — identical clients share a gene,
            # so their barrier/completion terms coincide (max is
            # idempotent) and only n_active needs the multiplicity.
            reps = [None] * n_genes
            for k, d in enumerate(devices):
                r = reps[profile_of[k]]
                if r is None:
                    reps[profile_of[k]] = d
                elif r != d:
                    # the collapsed evaluation would silently score a
                    # population that doesn't exist
                    raise ValueError(
                        f"devices sharing profile name {d.name!r} have "
                        f"different specs ({r} vs {d}); rename the "
                        "profile or set profile_based=False")
            self._counts = jnp.asarray(np.bincount(profile_of,
                                                   minlength=n_genes),
                                       jnp.float32)
            table_devices = reps
        else:
            self._counts = None
            table_devices = list(devices)
        self.tables = build_latency_tables(table_devices, server, batch,
                                           self.options)
        self._search = _get_search_fn(
            config.population_size, n_genes, len(self.options),
            config.generations, float(config.crossover_rate),
            float(config.mutation_rate), config.tournament_size,
            config.elitism, config.early_stop_patience,
            self._counts is not None)
        self._devices = list(devices)
        self._server = server
        self._batch = batch

    def run(self, key) -> SearchOut:
        """One full GA search from a device PRNG key. Device arrays in,
        device arrays out — safe under transfer_guard."""
        if self._counts is not None:
            return self._search(key, self.tables, self._counts)
        return self._search(key, self.tables)

    def to_result(self, out: SearchOut) -> GAResult:
        """Read back a SearchOut and re-evaluate the winning cuts
        through the host f64 model so the reported latency is exactly
        comparable with the numpy oracle's."""
        best_ind = np.asarray(out.best_ind)
        gens_run = int(out.generations_run)
        conv = int(out.convergence_gen)
        history = [float(h) for h in
                   np.asarray(out.history)[: gens_run + 1]]
        cuts = _upsample_cuts(best_ind, self.profile_of, self.n_clients,
                              self.options)
        latency = huscf_iteration_latency(cuts, self._devices,
                                          self._server, self._batch)
        # convention check: the converging generation's recorded best is
        # the final best (f32 tables vs host f64 -> loose tolerance)
        assert np.isclose(history[conv], float(out.best_latency),
                          rtol=1e-6), (history[conv], out.best_latency)
        return GAResult(cuts=cuts, latency=float(latency),
                        generations_run=gens_run, convergence_gen=conv,
                        history=history)


# ---------------------------------------------------------------------------
# host numpy oracle
# ---------------------------------------------------------------------------

def _fitness_factory(devices: Sequence[DeviceProfile],
                     server: DeviceProfile, batch: int,
                     profile_of: Optional[np.ndarray],
                     options: List[Cut]) -> Callable[[np.ndarray], float]:
    """individual: int array of option indices (per profile or per client)."""

    def fitness(ind: np.ndarray) -> float:
        cuts = _upsample_cuts(ind, profile_of, len(devices), options)
        return -huscf_iteration_latency(cuts, devices, server, batch)

    return fitness


def _optimize_cuts_host(devices: Sequence[DeviceProfile],
                        server: DeviceProfile, batch: int,
                        config: GAConfig) -> GAResult:
    options = all_cut_options()
    n_opt = len(options)
    rng = np.random.default_rng(config.seed)
    profile_of, n_genes = _profile_reduction(devices, config.profile_based)
    fitness = _fitness_factory(devices, server, batch, profile_of, options)

    pop = rng.integers(0, n_opt, size=(config.population_size, n_genes))
    fits = np.array([fitness(ind) for ind in pop])
    # generation 0: the initial population counts (history + best)
    best_fit = float(fits.max())
    best_ind = pop[int(np.argmax(fits))].copy()
    history: List[float] = [-best_fit]
    convergence_gen = 0
    stall = 0
    gen = 0

    # memoize fitness: the gene space is small under profile reduction
    cache: dict = {}

    def cached_fitness(ind: np.ndarray) -> float:
        key = ind.tobytes()
        if key not in cache:
            cache[key] = fitness(ind)
        return cache[key]

    for gen in range(1, config.generations + 1):
        # --- selection + crossover + mutation -> next generation
        order = np.argsort(-fits)
        elite = pop[order[: config.elitism]].copy()
        children = []
        while len(children) < config.population_size - config.elitism:
            def tournament():
                idx = rng.integers(0, config.population_size,
                                   config.tournament_size)
                return pop[idx[np.argmax(fits[idx])]]

            p1, p2 = tournament().copy(), tournament().copy()
            if rng.random() < config.crossover_rate and n_genes > 1:
                if rng.random() < 0.5:  # uniform
                    mask = rng.random(n_genes) < 0.5
                    p1[mask], p2[mask] = p2[mask].copy(), p1[mask].copy()
                else:  # two-point
                    a, b_ = sorted(rng.integers(0, n_genes, 2))
                    p1[a:b_ + 1], p2[a:b_ + 1] = (p2[a:b_ + 1].copy(),
                                                  p1[a:b_ + 1].copy())
            for child in (p1, p2):
                mut = rng.random(n_genes) < config.mutation_rate
                child[mut] = rng.integers(0, n_opt, int(mut.sum()))
                children.append(child)
        pop = np.vstack([elite, np.array(children[: config.population_size
                                                  - config.elitism])])
        fits = np.array([cached_fitness(ind) for ind in pop])

        gen_best = float(fits.max())
        history.append(-gen_best)
        if gen_best > best_fit + 1e-12:
            best_fit = gen_best
            best_ind = pop[int(np.argmax(fits))].copy()
            convergence_gen = gen
            stall = 0
        else:
            stall += 1
            if stall >= config.early_stop_patience:
                break

    # convention check: history[convergence_gen] is the final best
    assert history[convergence_gen] == -best_fit
    cuts = _upsample_cuts(best_ind, profile_of, len(devices), options)
    return GAResult(cuts=cuts, latency=-best_fit, generations_run=gen,
                    convergence_gen=convergence_gen, history=history)


def optimize_cuts(devices: Sequence[DeviceProfile],
                  server: DeviceProfile = PAPER_SERVER, *,
                  batch: int = 64, config: GAConfig = None,
                  fused: Optional[bool] = None) -> GAResult:
    """GA cut search. ``config.fused`` (overridable via the ``fused``
    kwarg) selects the device-resident path; the numpy oracle runs the
    same operators one scalar fitness call at a time."""
    config = config or GAConfig()
    if fused is not None:
        config = dataclasses.replace(config, fused=fused)
    if config.fused:
        searcher = CutSearcher(devices, server, batch=batch, config=config)
        out = searcher.run(jax.random.PRNGKey(config.seed))
        return searcher.to_result(out)
    return _optimize_cuts_host(devices, server, batch, config)


def exhaustive_profile_optimum(devices: Sequence[DeviceProfile],
                               server: DeviceProfile = PAPER_SERVER,
                               batch: int = 64) -> Tuple[List[Cut], float]:
    """Coordinate-descent-over-profiles sanity reference (not exact —
    barriers couple profiles — but a useful test bound for small
    populations).

    The full assignment is re-evaluated after every profile update and
    the (cuts, latency) snapshot is taken from that same evaluation, so
    the returned latency is by construction the latency *of the
    returned cuts* (the old mid-sweep snapshot could pair cuts with a
    latency measured for a different assignment)."""
    options = all_cut_options()
    names = [d.name for d in devices]
    uniq = sorted(set(names))
    best_global = None
    best_cuts = None
    # coordinate descent from a sensible start
    assign = {nm: options[0] for nm in uniq}
    for _ in range(4):
        for nm in uniq:
            best_local = None
            for opt in options:
                trial = dict(assign); trial[nm] = opt
                cuts = [trial[n_] for n_ in names]
                lat = huscf_iteration_latency(cuts, devices, server, batch)
                if best_local is None or lat < best_local[0]:
                    best_local = (lat, opt)
            assign[nm] = best_local[1]
            # re-evaluate the full updated assignment and snapshot cuts
            # + latency from the same evaluation
            cuts_now = [assign[n_] for n_ in names]
            lat_now = huscf_iteration_latency(cuts_now, devices, server,
                                              batch)
            if best_global is None or lat_now < best_global:
                best_global = lat_now
                best_cuts = cuts_now
    return best_cuts, best_global

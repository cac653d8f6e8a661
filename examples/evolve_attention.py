"""The paper's experiment: continuous autonomous evolution of the attention
kernel, scaled from 7 GPU-days to CPU-minutes.  Persists the lineage (the
git-commit-per-version analogue) and prints the Fig. 5/6-style trajectory.

Serial (paper §3.3, single lineage):
  PYTHONPATH=src python examples/evolve_attention.py                # MHA
  PYTHONPATH=src python examples/evolve_attention.py --gqa          # GQA transfer
  PYTHONPATH=src python examples/evolve_attention.py --commits 40   # paper-scale lineage

Island-model parallel (N concurrent lineages, migration, shared memory):
  PYTHONPATH=src python examples/evolve_attention.py --islands 4
  PYTHONPATH=src python examples/evolve_attention.py --islands 4 --scenario-sweep
  PYTHONPATH=src python examples/evolve_attention.py --islands 4 --eval-backend process
  PYTHONPATH=src python examples/evolve_attention.py --islands 4 --topology adaptive

Pipelined stepping (propose -> submit -> harvest; lineages identical to the
barrier engine) with an elastic worker-process pool and a shared speculative
prefetch budget:
  PYTHONPATH=src python examples/evolve_attention.py --islands 4 --pipeline
  PYTHONPATH=src python examples/evolve_attention.py --islands 4 --pipeline \
      --eval-backend process --elastic-workers 8 --prefetch-budget 16

Cross-host distributed scoring (loopback by default; bind --listen
0.0.0.0:PORT and workers on other hosts join with `python -m
repro.core.evals.service_worker --connect HOST:PORT`, and top-k migrant
payloads ride the same run):
  PYTHONPATH=src python examples/evolve_attention.py --islands 4 \
      --eval-backend service --workers 2 --listen 0.0.0.0:5123
  PYTHONPATH=src python examples/evolve_attention.py --islands 4 \
      --scenario-sweep --migrant-policy top-k --migrant-k 3
"""
import argparse
import os

import numpy as np

from repro.core import (AgenticVariationOperator, ContinuousEvolution,
                        IslandEvolution, ScriptedAgent, make_backend,
                        scenario_specs, topology_names)
from repro.core.perfmodel import expert_reference, fa_reference, gqa_suite, mha_suite
from repro.core.population import Lineage
from repro.launch.compile_cache import enable_compile_cache

OUT = os.path.join(os.path.dirname(__file__), "..", "results")


def run_serial(args):
    if args.gqa:
        mha_path = os.path.join(OUT, "lineage_mha.json")
        seed = (Lineage.load(mha_path).best().genome
                if os.path.exists(mha_path) else None)
        suite, path = gqa_suite(), os.path.join(OUT, "lineage_gqa.json")
        operator = AgenticVariationOperator(ScriptedAgent(seed=seed))
        print(f"adapting MHA-evolved genome to GQA: {seed}")
    else:
        suite, path = mha_suite(), os.path.join(OUT, "lineage_mha.json")
        operator = AgenticVariationOperator()

    backend_kw = ({"workers": args.workers, "listen": args.listen}
                  if args.eval_backend == "service" else {})
    evo = ContinuousEvolution(
        scorer=make_backend(args.eval_backend, suite=suite, **backend_kw),
        operator=operator, persist_path=path, pipeline=args.pipeline)
    if args.eval_backend == "service":
        host, port = evo.scorer.address
        print(f"evaluation service: {args.workers} local workers; more can "
              f"join with  python -m repro.core.evals.service_worker "
              f"--connect {host}:{port}")
    rep = evo.run(max_steps=args.max_steps, target_commits=args.commits,
                  verbose=True)

    traj = evo.lineage.trajectory()
    exp = float(np.exp(np.mean([np.log(expert_reference(c)) for c in suite])))
    fa = float(np.exp(np.mean([np.log(fa_reference(c)) for c in suite])))
    print(f"\n{rep.commits} commits / {rep.internal_attempts} internal "
          f"attempts / {rep.interventions} supervisor interventions")
    print(f"running-best geomean: {traj['running_best'][0]:.1f} -> "
          f"{traj['running_best'][-1]:.1f} TFLOPS "
          f"(expert line {exp:.1f}, FA line {fa:.1f})")
    print(f"best genome: {evo.lineage.best().genome}")
    print(f"lineage persisted to {path}")
    evo.close()


def run_islands(args):
    # one file per mode: sweep and homogeneous runs must not resume each other
    engine_kw = dict(seed=args.seed, prefetch=args.prefetch,
                     backend=args.eval_backend, topology=args.topology,
                     pipeline=args.pipeline,
                     elastic_workers=args.elastic_workers,
                     prefetch_budget=args.prefetch_budget,
                     migrant_policy=args.migrant_policy,
                     migrant_k=args.migrant_k)
    if args.eval_backend == "service":
        engine_kw["service_workers"] = args.workers
        engine_kw["service_listen"] = args.listen
    mode = "pipelined" if args.pipeline else "barrier"
    if args.scenario_sweep:
        path = os.path.join(OUT, "archipelago_sweep.json")
        engine = IslandEvolution.resume(path, specs=scenario_specs(),
                                        **engine_kw)
        print("scenario-sweep: islands "
              + ", ".join(i.name for i in engine.islands)
              + f"  (topology: {args.topology}, {mode} stepping)")
    else:
        path = os.path.join(OUT, "archipelago.json")
        engine = IslandEvolution.resume(path, n_islands=args.islands,
                                        suite=mha_suite(), **engine_kw)
        print(f"{args.islands} islands on the MHA suite, diverse inits "
              f"(topology: {args.topology}, {mode} stepping)")

    rep = engine.run(max_steps=args.max_steps,
                     target_commits=args.commits, verbose=True)
    print(f"\n{rep.commits} commits across {len(engine.islands)} islands / "
          f"{rep.internal_attempts} internal attempts / "
          f"{rep.migrations_accepted} migrations accepted")
    print(f"evaluations: {rep.evaluations} paid, {rep.cache_hits} shared-cache "
          f"hits" + (f", {rep.proposed} speculative proposals"
                     if args.pipeline else ""))
    if rep.eval_pool:
        p = rep.eval_pool
        if "grown" in p:                   # elastic process pool
            print(f"elastic pool: {p['workers']} workers now "
                  f"(peak {p['peak_workers']}, grew {p['grown']}x, shrank "
                  f"{p['shrunk']}x over {p['tasks_completed']} tasks)")
        else:                              # service coordinator registry
            print(f"eval service: {p['workers']} workers / "
                  f"{p['total_slots']} slots (peak {p['peak_workers']}, "
                  f"{p['joined']} joined / {p['left']} left, "
                  f"{p['tasks_requeued']} requeued over "
                  f"{p['tasks_completed']} tasks)")
    if engine.migration_stats.edges:
        rates = ", ".join(
            f"{engine.islands[s].name}->{engine.islands[d].name} "
            f"{st.accepts}/{st.attempts}"
            for (s, d), st in sorted(engine.migration_stats.edges.items()))
        print(f"migration acceptance per edge: {rates}")
    print(f"global best: {rep.best_geomean:.1f} TFLOPS on '{rep.best_island}'")
    print(f"scenario coverage geomean: {rep.coverage_geomean:.1f} TFLOPS")
    for name, r in rep.islands.items():
        print(f"  {name:14s} commits={r.commits:3d} best={r.best_geomean:7.1f} "
              f"interventions={r.interventions}")
    print(f"archipelago persisted to {path} (+ per-island files)")
    engine.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--commits", type=int, default=12)
    ap.add_argument("--max-steps", type=int, default=80)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gqa", action="store_true",
                    help="adapt the evolved MHA kernel to GQA (paper §4.3)")
    ap.add_argument("--islands", type=int, default=0,
                    help="run N islands in parallel instead of one lineage")
    ap.add_argument("--scenario-sweep", action="store_true",
                    help="one specialist island per suite (mha/gqa/decode)")
    ap.add_argument("--prefetch", type=int, default=0,
                    help="speculatively batch-evaluate this many KB candidate "
                         "edits per island step (cache warming on the scorer "
                         "executor; search results are unchanged)")
    ap.add_argument("--pipeline", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="propose -> submit -> harvest island stepping: "
                         "candidate batches are submitted to the eval backend "
                         "ahead of the authoritative walk and proposals span "
                         "the epoch barrier.  Lineages are identical to "
                         "--no-pipeline (the barrier engine); only wall-clock "
                         "and paid-evaluation counts change")
    ap.add_argument("--elastic-workers", type=int, default=0,
                    help="cap for an elastic worker-process pool that grows/"
                         "shrinks with eval queue depth (requires "
                         "--eval-backend process; 0 = fixed-size pool)")
    ap.add_argument("--prefetch-budget", type=int, default=None,
                    help="shared speculative-evaluation budget, re-divided "
                         "across islands each epoch from the KB's "
                         "predicted-gain distributions (replaces the static "
                         "--prefetch constant)")
    ap.add_argument("--topology", choices=topology_names(), default="ring",
                    help="migration graph for the island engine: ring (the "
                         "static default), star (hub = current best-coverage "
                         "island), all-to-all, or adaptive (acceptance-rate "
                         "EMAs prune dead edges and trial new ones on a "
                         "seeded schedule; exactly resumable)")
    ap.add_argument("--eval-backend",
                    choices=("inline", "thread", "process", "service"),
                    default=None,
                    help="evaluation service: inline (serial default), thread "
                         "(islands default), process — a warm worker-process "
                         "pool for real multi-core scaling of the correctness "
                         "checks — or service: cross-host scoring over socket "
                         "workers (--workers local ones; remote hosts join "
                         "with service_worker --connect).  Bit-identical "
                         "results; wall-clock only")
    ap.add_argument("--workers", type=int, default=2,
                    help="localhost worker processes to spawn for "
                         "--eval-backend service (0 = wait for external "
                         "workers to connect)")
    ap.add_argument("--listen", default="127.0.0.1:0",
                    help="bind address for the service coordinator; the "
                         "loopback default serves single-host fleets — use "
                         "0.0.0.0:PORT so workers on other hosts can join "
                         "(point them at this host's reachable name/IP)")
    ap.add_argument("--migrant-policy", choices=("best", "top-k"),
                    default="best",
                    help="what a donor island sends per migration edge: its "
                         "single best commit (default, the historical "
                         "behaviour) or its top-k distinct genomes — the "
                         "recipient re-scores all and adopts the best "
                         "survivor on its own suite")
    ap.add_argument("--migrant-k", type=int, default=3,
                    help="k for --migrant-policy top-k")
    args = ap.parse_args()
    enable_compile_cache()
    if args.eval_backend is None:
        args.eval_backend = ("thread" if args.islands or args.scenario_sweep
                             else "inline")

    os.makedirs(OUT, exist_ok=True)
    if args.islands or args.scenario_sweep:
        run_islands(args)
    else:
        run_serial(args)


if __name__ == "__main__":
    main()

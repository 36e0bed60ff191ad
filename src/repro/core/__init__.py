"""Core: the paper's contribution — FIN placement of early-exit DNNs.

Public API:
  system_model   — tiers / nodes / per-app slices (Plane 1)
  dnn_profile    — block/exit profiles (Plane 2), paper Tables II-IV
  extended_graph — single-plane extended graph with Eq. (1)-(2) weights
  feasible_graph — gamma-replicated FIN feasibility graph (Eq. 4 + pruning)
  fin / mcp / optimum — the three solvers compared in Sec. V
  problem        — configuration evaluation against (3a)-(3e)
  multiapp       — Sec. V multi-application orchestration
  capacity       — population-shared node/link capacity + congestion pricing
  contingency    — precomputed-failover library (O(1) failure masks)
"""
from .system_model import (NodeSpec, Network, make_node, make_network,
                           PAPER_TIERS, TPU_TIERS)
from .dnn_profile import (DNNProfile, ExitSpec, paper_profile, all_paper_apps,
                          profile_from_arch,
                          synthetic_profile, BITS_PER_FEATURE)
from .problem import (AppRequirements, Config, ConfigEval, Solution,
                      evaluate_config)
from .extended_graph import (ExtendedGraph, build_extended_graph,
                             build_extended_graphs, to_networkx)
from .feasible_graph import (FeasibleGraph, build_feasible_graph,
                             build_feasible_graphs)
from .fin import solve_fin, solve_many
from .frontier import (FrontierRow, ParetoFrontier, brute_force_frontier,
                       frontier_from_rows, pareto_mask)
from .plan import (Plan, PlanStats, solve_plans, update_uplinks,
                   migration_delta)
from .mcp import solve_mcp
from .optimum import solve_opt
from .multiapp import (run_multiapp, MultiAppResult, AppStats, PlanCache,
                       PAPER_MULTIAPP_REQS, app_price_weights,
                       default_solvers, user_network, user_networks)
from .scenarios import ChurnEvent, churn_trace
from .population import Population, PopulationStats
from .capacity import (SharedCapacity, CongestionController,
                       CongestionReport, accumulate_loads, config_load_rows)
from .contingency import (ContingencyEntry, ContingencyLibrary,
                          ContingencyPolicy, ContingencyStats,
                          NoFeasiblePlacement, PopulationContingency,
                          candidate_masks, tier_groups_of)
from .online import (ChurnOrchestrator, ChurnStats, TickReport,
                     population_cohorts, population_plans)

__all__ = [
    "NodeSpec", "Network", "make_node", "make_network", "PAPER_TIERS",
    "TPU_TIERS", "DNNProfile", "ExitSpec", "paper_profile", "all_paper_apps",
    "profile_from_arch",
    "synthetic_profile", "BITS_PER_FEATURE", "AppRequirements", "Config",
    "ConfigEval", "Solution", "evaluate_config", "ExtendedGraph",
    "build_extended_graph", "build_extended_graphs", "to_networkx",
    "FeasibleGraph", "build_feasible_graph", "build_feasible_graphs",
    "solve_fin", "solve_many",
    "FrontierRow", "ParetoFrontier", "brute_force_frontier",
    "frontier_from_rows", "pareto_mask",
    "Plan", "PlanStats", "solve_plans", "update_uplinks", "migration_delta",
    "solve_mcp",
    "solve_opt", "run_multiapp", "MultiAppResult", "AppStats",
    "PAPER_MULTIAPP_REQS", "default_solvers", "user_network",
    "user_networks", "PlanCache",
    "ChurnEvent", "churn_trace", "ChurnOrchestrator", "ChurnStats",
    "TickReport", "population_plans", "population_cohorts",
    "Population", "PopulationStats",
    "SharedCapacity", "CongestionController", "CongestionReport",
    "accumulate_loads", "config_load_rows", "app_price_weights",
    "ContingencyEntry", "ContingencyLibrary", "ContingencyPolicy",
    "ContingencyStats", "NoFeasiblePlacement", "PopulationContingency",
    "candidate_masks", "tier_groups_of",
]

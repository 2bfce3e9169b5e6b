from repro.swarm.neighbors import comm_range_m, grid_geometry, neighbor_lists
from repro.swarm.scenario import (CHANNEL_EDGE_MODELS, CHANNEL_MODELS,
                                  FAULT_MODELS, MOBILITY_MODELS, get_channel,
                                  get_channel_edges, get_fault, get_mobility,
                                  register_channel, register_channel_edges,
                                  register_fault, register_mobility)
from repro.swarm.simulator import (DISTRIBUTED, GREEDY, LOCAL_ONLY, RANDOM,
                                   RANDOM_ACYCLIC, STRATEGY_NAMES, run_many,
                                   run_sim)
from repro.swarm.tasks import TaskProfile, make_profile

__all__ = ["run_sim", "run_many", "make_profile", "TaskProfile",
           "LOCAL_ONLY", "RANDOM", "RANDOM_ACYCLIC", "GREEDY", "DISTRIBUTED",
           "STRATEGY_NAMES",
           "MOBILITY_MODELS", "CHANNEL_MODELS", "CHANNEL_EDGE_MODELS",
           "FAULT_MODELS",
           "register_mobility", "register_channel", "register_channel_edges",
           "register_fault", "get_mobility", "get_channel",
           "get_channel_edges", "get_fault", "neighbor_lists", "comm_range_m",
           "grid_geometry"]

"""path_stall_s_per_dag: see path_stall_s_per_dag.json."""
import path_metrics


def read(obs):
    return path_metrics.path_s_per_dag(obs, "stall")

"""dag_turnaround_s_per_dag: see dag_turnaround_s_per_dag.json."""
import path_metrics


def read(obs):
    return path_metrics.dag_turnaround_s(obs)

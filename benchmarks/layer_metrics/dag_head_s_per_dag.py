"""dag_head_s_per_dag: see dag_head_s_per_dag.json."""
import path_metrics


def read(obs):
    return path_metrics.dag_head_s(obs)

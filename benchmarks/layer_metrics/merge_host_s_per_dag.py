"""merge_host_s_per_dag: see merge_host_s_per_dag.json."""
import span_metrics


def read(obs):
    return span_metrics.self_s_per_dag(obs, ("merge.stage", "merge.launch", "merge.gather"))

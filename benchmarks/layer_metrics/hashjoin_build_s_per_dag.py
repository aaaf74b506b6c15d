"""hashjoin_build_s_per_dag: see hashjoin_build_s_per_dag.json."""
import span_metrics


def read(obs):
    return span_metrics.self_s_per_dag(obs, ("join.build",))

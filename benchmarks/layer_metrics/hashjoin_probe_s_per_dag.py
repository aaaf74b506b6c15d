"""hashjoin_probe_s_per_dag: see hashjoin_probe_s_per_dag.json."""
import span_metrics


def read(obs):
    return span_metrics.self_s_per_dag(
        obs, ("join.probe", "join.match", "join.emit"))

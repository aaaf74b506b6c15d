"""scan_s_per_dag: see scan_s_per_dag.json."""
import span_metrics


def read(obs):
    return span_metrics.self_s_per_dag(obs, ("scan.read", "scan.filter"))

"""exchange_wait_peers_s_per_dag: see exchange_wait_peers_s_per_dag.json."""
import span_metrics


def read(obs):
    return span_metrics.self_s_per_dag(obs, ("exchange.wait_peers",))

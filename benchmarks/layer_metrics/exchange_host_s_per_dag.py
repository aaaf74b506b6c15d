"""exchange_host_s_per_dag: see exchange_host_s_per_dag.json."""
import span_metrics


def read(obs):
    return span_metrics.self_s_per_dag(obs, ("exchange.plan", "exchange.pack", "exchange.decode"))

"""span_sort_wait_s_per_dag: see span_sort_wait_s_per_dag.json."""
import span_metrics


def read(obs):
    return span_metrics.histograms_s_per_dag(obs, ("device.dispatch_wait", "device.d2h"))

"""merge_device_wait_s_per_dag: see merge_device_wait_s_per_dag.json."""
import span_metrics


def read(obs):
    return span_metrics.self_s_per_dag(obs, ("merge.readback",))

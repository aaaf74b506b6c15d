"""join_wait_inputs_s_per_dag: see join_wait_inputs_s_per_dag.json."""
import span_metrics


def read(obs):
    return span_metrics.self_s_per_dag(obs, ("join.wait_inputs",))

"""dag_untasked_max_s: see dag_untasked_max_s.json."""
import span_metrics


def read(obs):
    return span_metrics.dag_untasked_max_s(obs)

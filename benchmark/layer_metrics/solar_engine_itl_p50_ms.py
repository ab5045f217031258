"""model runner: ``engine_itl_p50_ms`` in the cell of the ``solar-open2-250b-ep8-1chip`` configuration. The
accepted entry's ``workloads`` list is closed to a ``model_config`` PR, so the same
``read`` is imported under a name of its own, not copied (PERF.md section 7: an alias to
delete when a ``benchmark`` PR opens that list)."""

from layer_metrics.engine_itl_p50_ms import read  # noqa: F401

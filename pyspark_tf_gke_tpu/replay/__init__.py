"""Trace-driven workload replay and capacity planning.

The load-generation plane: the reference platform drives all workload
submission from a bastion coordinator outside the cluster (PAPER.md
L6); this package is that idea reborn for the serving plane. A
**workload spec** (``spec.py``) is a versioned JSONL file of request
shapes — arrival offset, tenant, prompt/output lengths, prefix group,
deadline — produced either from a ``GET /traces`` export
(``extract.py``) or from synthetic generators (``generators.py``:
diurnal waves, flash crowds, adversarial tenant floods, long-tail
prompt mixes, shared-prefix clusters). The **replay driver**
(``driver.py``) fires a spec open-loop against any base URL at a
configurable speed-up, capturing streaming TTFT/TBT per request, and
``slo.py`` turns the resulting report into machine-readable pass/fail
SLO verdicts. The **capacity model** (``capacity.py``) predicts queue
delay, p99 latency and shed counts for the same spec from the
``/loadz`` math the router's autoscale signal uses — so HPA metric
targets become derived numbers, and prediction-vs-replay agreement is
an assertable contract (``tools/smoke_check.py --replay``).

Everything here is stdlib-only and jax-free: the replay plane must run
from a bastion host without initializing a device backend. New scenario = new spec file, not new harness code.
"""

from pyspark_tf_gke_tpu.replay.capacity import (  # noqa: F401
    FleetModel,
    check_agreement,
    derive_hpa_targets,
    predict,
)
from pyspark_tf_gke_tpu.replay.driver import replay_spec  # noqa: F401
from pyspark_tf_gke_tpu.replay.extract import (  # noqa: F401
    spec_from_traces,
)
from pyspark_tf_gke_tpu.replay.generators import (  # noqa: F401
    GENERATORS,
    synth_spec,
)
from pyspark_tf_gke_tpu.replay.slo import evaluate_slo  # noqa: F401
from pyspark_tf_gke_tpu.replay.spec import (  # noqa: F401
    SPEC_VERSION,
    SpecRequest,
    WorkloadSpec,
)

from repro.serve.admission import (SHED_POLICIES, AdmissionQueue,  # noqa
                                   AdmissionRejected, Rejection)
from repro.serve.engine import (MASKED_FAMILIES, SERVE_SPANS,  # noqa
                                TERMINAL_STATUSES, BatchScheduler, Engine,
                                Request, ServeConfig)
from repro.serve.kv_pool import KVPool  # noqa

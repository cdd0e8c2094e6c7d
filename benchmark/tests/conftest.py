"""The benchmark's own tests run on the CPU at tiny sizes (the harness's
look for a chip is skipped by run.main(allow_cpu=True))."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# tiny sizes of each cell, set over its configuration and traffic files
TINY = {
    "msmarco-passage.batch-bm25-k1000": {
        "passages": 3000, "block_queries": 16, "pool_blocks": 2,
        "check_sample": 20},
    "robust04.build": {"documents": 400, "topics": 30, "batch_docs": 200},
}

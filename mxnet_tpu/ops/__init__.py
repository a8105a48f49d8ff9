"""Op corpus: importing this package populates the registry."""
from . import tensor, nn, optimizer_ops, linalg, rnn, ctc  # noqa: F401
from . import contrib_ops, image_ops, quantization, random_ops  # noqa: F401
from . import control_flow  # noqa: F401
from . import extra_ops, numpy_ops, short_conv  # noqa: F401
from . import fused  # noqa: F401  (graph-optimizer rewrite targets)
from . import legacy_aliases  # noqa: F401  (must import after all op modules)
from .registry import get_op, list_ops, make_nd_function, register_op  # noqa: F401

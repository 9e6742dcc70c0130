from .ops import featurize_op
from .kernel import featurize_pallas

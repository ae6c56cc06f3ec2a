"""Model families ported from the reference apps (SURVEY.md §2.3), as pure
JAX scoring/loss functions pluggable into ops.fused."""
from .kge import (complex_eval_scores, complex_score, make_kge_loss,  # noqa
                  rescal_score)
from .mf import (col_key, full_loss, make_mf_loss, mf_sq_error,  # noqa
                 row_key)
from .sgns import sgns_loss, syn0_key, syn1_key  # noqa

"""Applied rematerialization: turn the analyzer's ``remat-opportunity``
suggestion (or an explicitly named policy) into the ``jax.checkpoint``
wrapper the fused train step actually runs under.

PR 8's efficiency auditor can *name* the right ``jax.checkpoint`` policy
for a graph (``Report.extras["remat"]``) but nothing acted on it; this
module closes that loop behind one knob:

``MXNET_TPU_REMAT = off | auto | <policy-name>``

* ``off`` (default) — save all activations; this module is never
  imported on the hot path.
* ``auto`` — run the analysis graph passes over the bound symbol and
  apply exactly the policy the ``remat-opportunity`` pass suggests for
  THIS graph (``extras["remat"]["suggestion"]["policy"]``). No
  suggestion (nothing worth rematerializing) means no wrapping.
* anything else — a ``jax.checkpoint_policies`` attribute name applied
  as-is (``nothing_saveable``, ``dots_with_no_batch_dims_saveable``,
  ``dots_saveable``, ...). Unknown names raise at bind, naming the
  valid choices, instead of silently training without remat.

The legacy bool ``MXNET_EXEC_ENABLE_REMAT=1`` is kept as an alias for
``dots_with_no_batch_dims_saveable`` (its documented historical
behavior) and loses to an explicit ``MXNET_TPU_REMAT``.

Application point (``Module._build_fused_step`` and the executor's
``fwd_bwd``): the whole forward is wrapped under the policy.
``remat_applied`` counts every build that actually wrapped,
and the chosen policy is surfaced via the ``remat_policy`` extra in
``mx.obs.report()``'s counters companion gauges.
"""
from __future__ import annotations

import logging
from typing import Any, Optional, Tuple

from .base import MXNetError

__all__ = ["resolve_policy"]

log = logging.getLogger(__name__)


def _policy_by_name(name: str):
    import jax
    pol = getattr(jax.checkpoint_policies, name, None)
    if pol is None or name.startswith("_"):
        valid = sorted(p for p in dir(jax.checkpoint_policies)
                       if not p.startswith("_"))
        raise MXNetError(
            "MXNET_TPU_REMAT=%r is not a jax.checkpoint_policies name; "
            "valid policies: %s (or off/auto)" % (name, ", ".join(valid)))
    return pol


def resolve_policy(symbol=None, input_shapes=None, input_dtypes=None
                   ) -> Tuple[Optional[Any], str]:
    """Resolve the active remat policy for a bind: ``(policy, name)``,
    where ``policy`` is a jax saveable-predicate (None = remat off).
    ``auto`` consumes the analyzer's suggestion for ``symbol`` directly;
    it needs the bound shapes to rank candidates."""
    from . import config as _config
    mode = _config.get("MXNET_TPU_REMAT")
    if mode == "off":
        if _config.get("MXNET_EXEC_ENABLE_REMAT"):
            # legacy alias (docs/env_var.md): the historical fused-step
            # save-policy form
            name = "dots_with_no_batch_dims_saveable"
            return _policy_by_name(name), name
        return None, "off"
    if mode != "auto":
        return _policy_by_name(mode), mode
    if symbol is None:
        return None, "off"
    from .analysis import analyze_symbol
    # only the policy NAME is consumed here
    report = analyze_symbol(symbol, input_shapes=input_shapes,
                            input_dtypes=input_dtypes,
                            context="remat-auto")
    remat = report.extras.get("remat") or {}
    suggestion = remat.get("suggestion") or {}
    name = suggestion.get("policy")
    if not name:
        log.info("MXNET_TPU_REMAT=auto: remat-opportunity found nothing "
                 "worth rematerializing; running without checkpoint")
        return None, "off"
    return _policy_by_name(name), "auto:%s" % name

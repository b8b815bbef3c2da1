"""reprolint — an AST-based invariant checker for this repo's JAX/Pallas code.

Every correctness claim in the reproduction rests on fragile trace-time
invariants (bitwise parity, donation, PRNG stream coherence, structural
agreement across ``lax.cond`` branches), and PRs 4-8 each shipped a bugfix
for a violated one.  reprolint turns those recurring bug classes into
machine-checked rules:

========  ==============================================================
RL001     host sync (``float()``/``.item()``/``np.asarray``) in traced code
RL002     ``vmap`` applied to a function containing ``pallas_call``
RL003     ``lax.cond``/``switch`` branches that disagree structurally
RL004     donated-buffer reuse after a ``donate_argnums`` jitted call
RL005     import layering (from ``layers.toml``, the single source of truth)
RL006     PRNG key consumed twice without an intervening ``split``
RL007     Python ``if``/``while`` on a traced value
========  ==============================================================

Run ``python -m tools.reprolint src tests``; see
``docs/static-analysis.md`` for rule rationale, suppression syntax and the
recipe for adding a rule.
"""
from .context import ModuleContext
from .engine import Finding, Linter
from .layers import LayerMap
from .rules import Rule, all_rules

__all__ = ["Finding", "LayerMap", "Linter", "ModuleContext", "Rule", "all_rules"]

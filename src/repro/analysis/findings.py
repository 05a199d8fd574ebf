"""Structured lint findings.

A finding pins one violation to (rule, entry point, op, path into the
jaxpr, user source site) so a CI failure is actionable without re-running
anything locally.  ``severity`` is ``"error"`` (fails the CLI) or
``"warning"`` (printed, does not fail).
"""
from __future__ import annotations

from dataclasses import dataclass


class Severity:
    ERROR = "error"
    WARNING = "warning"


@dataclass
class Finding:
    rule: str            # registry name, e.g. "no-scatter"
    severity: str        # Severity.ERROR | Severity.WARNING
    entry: str           # entry-point name, e.g. "fleet.controller_chunk"
    message: str         # human-readable statement of the violation
    op: str = ""         # primitive / HLO opcode involved
    path: str = ""       # source path into the jaxpr, e.g. "pjit/scan[1]/eqn[42]"
    site: str = ""       # user code site, e.g. "install_window_values @ pipeline.py:308"

    def format(self) -> str:
        loc = f" [{self.path}]" if self.path else ""
        at = f" at {self.site}" if self.site else ""
        op = f" ({self.op})" if self.op else ""
        return (f"{self.severity.upper()} {self.rule} {self.entry}{op}: "
                f"{self.message}{at}{loc}")


def errors(findings) -> list:
    return [f for f in findings if f.severity == Severity.ERROR]

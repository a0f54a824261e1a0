"""What every app's job shares: the chunk the window drives through
``StradsEngine.execute``, resumed from the previous report's carry."""
from __future__ import annotations

from repro.core import ExecutionPlan


class EngineJob:
    """Subclasses set ``eng``, ``state``, ``data``, ``key`` and
    ``rounds_per_chunk``; one whose plan is not the app's default
    overrides :meth:`plan`."""

    carry = None

    def plan(self, total_rounds: int) -> ExecutionPlan:
        return ExecutionPlan(executor="scan", rounds=total_rounds)

    def run_chunk(self):
        """One chunk: ``rounds_per_chunk`` more rounds of the same plan,
        resumed from the carry, so every chunk runs one program."""
        done = 0 if self.carry is None else int(self.carry.t)
        return self.eng.execute(self.state, self.data, self.key,
                                self.plan(done + self.rounds_per_chunk),
                                carry=self.carry)

    def take(self, rep):
        self.state, self.carry = rep.state, rep.carry

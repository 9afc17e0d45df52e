from repro.runtime.fault_tolerance import (FailureSchedule, SimulatedFailure,
                                           Stage, StagedState, StageSchedule,
                                           Supervisor, SupervisorResult,
                                           run_staged, staged_from_host,
                                           staged_to_host)

__all__ = ["FailureSchedule", "SimulatedFailure", "Stage", "StagedState",
           "StageSchedule", "Supervisor", "SupervisorResult", "run_staged",
           "staged_from_host", "staged_to_host"]

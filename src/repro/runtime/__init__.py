from .failures import FailureInjector, FailureEvent, Heartbeat

__all__ = ["FailureInjector", "FailureEvent", "Heartbeat"]

from . import spans
from .supervisor import (StragglerWatchdog, Supervisor, SupervisorConfig,
                         WatchdogEvent)

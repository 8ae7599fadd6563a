"""Framework layer: session lifecycle, statements, config
(SURVEY.md §2.1 framework row; reference pkg/scheduler/framework/)."""

from .conf import DEFAULT_ACTIONS, DEFAULT_PLUGINS, PluginConfig, \
    SchedulerConfig
from .propose import Proposal
from .session import InMemoryCache, SchedulableResult, Session
from .statement import Statement

__all__ = ["DEFAULT_ACTIONS", "DEFAULT_PLUGINS", "PluginConfig",
           "SchedulerConfig", "InMemoryCache", "Proposal",
           "SchedulableResult", "Session", "Statement"]

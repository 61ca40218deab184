from repro.serve import broker, cache, retry, trajectory  # noqa: F401
from repro.serve.broker import (  # noqa: F401
    AdmissionError, DeadlineExceededError, Degradation, GroupSlice,
    QueryBroker, QueryTicket, TicketHealth)
from repro.serve.cache import CacheStats, SliceCache  # noqa: F401
from repro.serve.retry import RetryPolicy  # noqa: F401
from repro.serve.trajectory import (  # noqa: F401
    QueryRequest, QueryResponse, TrajectoryQueryService)

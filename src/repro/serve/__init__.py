from .ph import (AdmissionDecision, PHRequest, PHResponse, PHServeEngine,
                 fingerprint_points)

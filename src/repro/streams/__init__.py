from repro.streams.admission import (AdmissionConfig, AdmissionController,
                                     AdmissionDecision, AdmissionState,
                                     admission_row)
from repro.streams.app import StreamApp, demo_apps
from repro.streams.router import (PodSlice, StreamRouter, build_cluster,
                                  default_slices)

__all__ = ["AdmissionConfig", "AdmissionController", "AdmissionDecision",
           "AdmissionState", "admission_row",
           "StreamApp", "demo_apps", "PodSlice", "StreamRouter",
           "build_cluster", "default_slices"]

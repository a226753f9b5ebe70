"""On-chip benchmark of the Floating Gossip simulator (see PERF.md)."""

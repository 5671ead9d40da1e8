// sgcnpart — multilevel k-way graph and column-net hypergraph partitioners.
//
// TPU-era replacement for the capabilities the reference gets from vendored
// METIS (GCN-GP/main.cpp:290-348, METIS_PartGraphKway, edge-cut objective) and
// PaToH/KaHyPar (GCN-HP/main.cpp:284-356, column-net model, connectivity-1
// objective).  We cannot redistribute those libraries, so this is our own
// implementation of the same algorithm family:
//
//   graph:      heavy-edge-matching coarsening -> greedy k-way growing on the
//               coarsest graph -> greedy boundary refinement on each level
//               (edge-cut objective, balance constraint).
//   hypergraph: heavy-connectivity matching on cells -> greedy growing ->
//               boundary FM-style km1 refinement with per-net part-pin counts
//               (connectivity-1 objective; cells = matrix rows weighted by
//               nnz, nets = columns — the column-net model of the reference).
//
// Exposed as a C ABI for ctypes (sgcn_tpu/partition/native.py) and as a small
// CLI (main() at the bottom) mirroring the reference partitioner executables.
//
// Quality bar (SURVEY.md §7.1): self-reported cut / lambda-1 must beat random
// partitioning by a wide margin and respect the balance constraint; bit-parity
// with METIS/PaToH is a non-goal.

#include <algorithm>
#include <chrono>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <numeric>
#include <queue>
#include <string>
#include <tuple>
#include <unordered_map>
#include <vector>

namespace {

using i32 = int32_t;
using i64 = int64_t;

// Portable deterministic RNG (splitmix64).  std::shuffle /
// std::uniform_int_distribution are implementation-defined mappings, so
// seeded partitions would differ across standard libraries; every draw here
// is pinned to this generator instead.
struct Rng {
  uint64_t s;
  explicit Rng(uint64_t seed) : s(seed) {}
  uint64_t next() {
    s += 0x9E3779B97F4A7C15ULL;
    uint64_t z = s;
    z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ULL;
    z = (z ^ (z >> 27)) * 0x94D049BB133111EBULL;
    return z ^ (z >> 31);
  }
  // uniform in [0, n); modulo bias is irrelevant at these magnitudes
  uint64_t below(uint64_t n) { return n ? next() % n : 0; }
};

template <typename T>
void fy_shuffle(std::vector<T>& v, Rng& rng) {
  for (size_t i = v.size(); i > 1; --i)
    std::swap(v[i - 1], v[rng.below(i)]);
}

struct Graph {
  i32 n = 0;
  std::vector<i64> xadj;    // n+1
  std::vector<i32> adj;     // neighbor ids
  std::vector<float> wgt;   // edge weights
  std::vector<i64> vwgt;    // vertex weights
  i64 total_vwgt = 0;
};

// ---------------------------------------------------------------- coarsening
struct MatchResult {
  std::vector<i32> cmap;    // fine vertex -> coarse vertex
  i32 cn = 0;
};

MatchResult heavy_edge_matching(const Graph& g, Rng& rng) {
  std::vector<i32> order(g.n);
  std::iota(order.begin(), order.end(), 0);
  fy_shuffle(order, rng);
  std::vector<i32> match(g.n, -1);
  for (i32 v : order) {
    if (match[v] != -1) continue;
    i32 best = -1;
    float best_w = -1.0f;
    for (i64 e = g.xadj[v]; e < g.xadj[v + 1]; ++e) {
      i32 u = g.adj[e];
      if (u == v || match[u] != -1) continue;
      if (g.wgt[e] > best_w) { best_w = g.wgt[e]; best = u; }
    }
    if (best != -1) { match[v] = best; match[best] = v; }
    else match[v] = v;
  }
  MatchResult r;
  r.cmap.assign(g.n, -1);
  for (i32 v = 0; v < g.n; ++v) {
    if (r.cmap[v] != -1) continue;
    i32 u = match[v];
    r.cmap[v] = r.cn;
    if (u != v && u != -1) r.cmap[u] = r.cn;
    ++r.cn;
  }
  return r;
}

Graph contract(const Graph& g, const MatchResult& m) {
  Graph c;
  c.n = m.cn;
  c.vwgt.assign(m.cn, 0);
  for (i32 v = 0; v < g.n; ++v) c.vwgt[m.cmap[v]] += g.vwgt[v];
  c.total_vwgt = g.total_vwgt;
  c.xadj.assign(m.cn + 1, 0);
  // bucket fine vertices by coarse id
  std::vector<i32> fine_of(g.n);
  std::vector<i64> cstart(m.cn + 1, 0);
  for (i32 v = 0; v < g.n; ++v) cstart[m.cmap[v] + 1]++;
  for (i32 cv = 0; cv < m.cn; ++cv) cstart[cv + 1] += cstart[cv];
  {
    std::vector<i64> pos(cstart.begin(), cstart.end() - 1);
    for (i32 v = 0; v < g.n; ++v) fine_of[pos[m.cmap[v]]++] = v;
  }
  std::unordered_map<i32, float> nbr;
  nbr.reserve(256);
  for (i32 cv = 0; cv < m.cn; ++cv) {
    nbr.clear();
    for (i64 p = cstart[cv]; p < cstart[cv + 1]; ++p) {
      i32 v = fine_of[p];
      for (i64 e = g.xadj[v]; e < g.xadj[v + 1]; ++e) {
        i32 cu = m.cmap[g.adj[e]];
        if (cu == cv) continue;
        nbr[cu] += g.wgt[e];
      }
    }
    c.xadj[cv + 1] = c.xadj[cv] + (i64)nbr.size();
    for (auto& kv : nbr) { c.adj.push_back(kv.first); c.wgt.push_back(kv.second); }
  }
  return c;
}

// ------------------------------------------------------- initial partitioning
// Greedy k-way growing: spread seeds, grow parts by absorbing the frontier
// vertex with the strongest connection to the part, under the balance cap.
void greedy_grow(const Graph& g, int k, double cap, std::vector<i32>& part,
                 Rng& rng) {
  part.assign(g.n, -1);
  std::vector<i64> pw(k, 0);
  std::vector<float> conn(g.n, 0.0f);   // connection of v to the growing part
  std::vector<i32> order(g.n);
  std::iota(order.begin(), order.end(), 0);
  fy_shuffle(order, rng);
  size_t cursor = 0;
  for (int p = 0; p < k; ++p) {
    // seed: first unassigned vertex in the shuffled order
    while (cursor < order.size() && part[order[cursor]] != -1) ++cursor;
    if (cursor >= order.size()) break;
    i32 seed = order[cursor];
    std::fill(conn.begin(), conn.end(), 0.0f);
    std::vector<i32> frontier{seed};
    part[seed] = p; pw[p] += g.vwgt[seed];
    // grow until this part reaches total/k (leave slack for the last parts)
    i64 target = g.total_vwgt / k;
    while (pw[p] < target) {
      // refresh connections from newly absorbed vertices
      for (i32 v : frontier)
        for (i64 e = g.xadj[v]; e < g.xadj[v + 1]; ++e) {
          i32 u = g.adj[e];
          if (part[u] == -1) conn[u] += g.wgt[e];
        }
      frontier.clear();
      // pick best-connected unassigned vertex (linear scan; coarsest graph is small)
      i32 best = -1; float best_c = -1.0f;
      for (i32 u = 0; u < g.n; ++u)
        if (part[u] == -1 && conn[u] > best_c) { best_c = conn[u]; best = u; }
      if (best == -1 || best_c <= 0.0f) {
        // disconnected: jump to any unassigned vertex
        for (i32 u = 0; u < g.n; ++u) if (part[u] == -1) { best = u; break; }
        if (best == -1) break;
      }
      if (pw[p] + g.vwgt[best] > (i64)(cap)) break;
      part[best] = p; pw[p] += g.vwgt[best];
      frontier.push_back(best);
    }
  }
  // leftovers -> lightest part
  for (i32 v = 0; v < g.n; ++v)
    if (part[v] == -1) {
      int lp = (int)(std::min_element(pw.begin(), pw.end()) - pw.begin());
      part[v] = lp; pw[lp] += g.vwgt[v];
    }
}

// ------------------------------------------------------------- refinement
// Edge-cut refinement state shared by the sweep and FM phases — the graph-side
// mirror of Km1Refiner below (same structure: greedy boundary sweeps carry the
// bulk, a lazy-heap FM hill-climbing pass escapes local minima where size
// affords it).  Role parity: the refinement inside METIS_PartGraphKway
// (GCN-GP/main.cpp:334) is this same KL/FM family.
struct CutRefiner {
  const Graph& g;
  const int k;
  const double cap;
  std::vector<i32>& part;
  std::vector<i64> pw;
  std::vector<float> conn;   // scratch: weight of v's edges into each part

  CutRefiner(const Graph& g_, int k_, double cap_, std::vector<i32>& part_)
      : g(g_), k(k_), cap(cap_), part(part_), conn(k_) {
    pw.assign(k, 0);
    for (i32 v = 0; v < g.n; ++v) pw[part[v]] += g.vwgt[v];
  }

  // Best feasible move for v: cut gain = conn[target] - conn[current].
  // Ties prefer the lighter target part.  target = -1 when v is interior or
  // no part has room.
  float best_move(i32 v, i32& target) {
    const int pv = part[v];
    std::fill(conn.begin(), conn.end(), 0.0f);
    bool boundary = false;
    for (i64 e = g.xadj[v]; e < g.xadj[v + 1]; ++e) {
      int pu = part[g.adj[e]];
      conn[pu] += g.wgt[e];
      boundary |= pu != pv;
    }
    target = -1;
    float best_gain = 0.0f;
    for (int p = 0; p < k; ++p) {
      if (p == pv) continue;
      if (pw[p] + g.vwgt[v] > (i64)cap) continue;
      float d = conn[p] - conn[pv];
      if (target == -1 || d > best_gain ||
          (d == best_gain && pw[p] < pw[target])) {
        best_gain = d; target = p;
      }
    }
    if (!boundary) target = -1;
    return target == -1 ? 0.0f : best_gain;
  }

  void apply(i32 v, i32 to) {
    pw[part[v]] -= g.vwgt[v]; pw[to] += g.vwgt[v];
    part[v] = to;
  }

  using Gain = float;
  i32 n_items() const { return g.n; }

  // Greedy boundary passes applying only positive-gain moves (the default
  // greedy variant of KL/FM refinement of the METIS family).
  void sweeps(int max_passes) {
    for (int pass = 0; pass < max_passes; ++pass) {
      i64 moves = 0;
      for (i32 v = 0; v < g.n; ++v) {
        i32 t; float gn = best_move(v, t);
        if (t >= 0 && gn > 0.0f) { apply(v, t); ++moves; }
      }
      if (moves == 0) break;
    }
  }
};

// One FM hill-climbing pass, shared by the cut and km1 refiners (the
// gain-ordered refinement of the KL/FM–PaToH family).  A lazy max-heap
// replaces classic gain-bucket arrays — k-way gains are not small bounded
// integers, and the heap keeps the balance-aware tie-break explicit:
//   * seed with every boundary item's best feasible move,
//   * repeatedly apply the globally best move, negative gains included
//     (the hill-climbing a greedy sweep lacks), locking moved items,
//   * remember the best prefix of the move sequence, roll back past it.
// Deterministic: no randomness; heap ties resolve on (gain, item, target).
// Stale heap entries revalidate on pop; neighbors are NOT eagerly requeued
// (on coarse instances a merged item touches thousands of nets and eager
// requeue is quadratic per move) — the surrounding pass loop reseeds the
// heap from scratch, so improved items are only serviced slightly later.
// Cost is bounded (drift window + pop cap) so multilevel drivers can afford
// it above the coarsest level.  R exposes n_items(), best_move(v, target&),
// apply(v, to), part, and a Gain type.
template <typename R>
typename R::Gain fm_pass(R& r) {
  using Gain = typename R::Gain;
  struct Move { i32 item, from; };
  using Entry = std::tuple<Gain, i32, i32>;         // (gain, item, target)
  const i32 n = r.n_items();
  std::priority_queue<Entry> heap;
  std::vector<char> locked(n, 0);
  for (i32 v = 0; v < n; ++v) {
    i32 t; Gain gn = r.best_move(v, t);
    if (t >= 0) heap.emplace(gn, v, t);
  }
  std::vector<Move> moves;
  Gain cum = 0, best_cum = 0;
  size_t best_len = 0;
  int since_best = 0;
  const int drift =                                 // hill-climb tolerance
      std::max(30, std::min(n / 16, 256));
  // Stale-entry revalidation pops don't advance since_best; cap total pops
  // so adversarial churn (many requeues between applies) stays bounded.
  size_t pops = 0;
  const size_t pop_cap = 16u * (size_t)n + 1024;
  while (!heap.empty() && since_best < drift && pops++ < pop_cap &&
         moves.size() < (size_t)n) {
    auto [gn, v, t] = heap.top(); heap.pop();
    if (locked[v]) continue;
    i32 t2; Gain g2 = r.best_move(v, t2);
    if (t2 < 0) continue;
    if (g2 != gn || t2 != t) {                      // stale: requeue current
      heap.emplace(g2, v, t2);
      continue;
    }
    moves.push_back({v, r.part[v]});
    r.apply(v, t);
    locked[v] = 1;
    cum += gn;
    if (cum > best_cum) { best_cum = cum; best_len = moves.size(); since_best = 0; }
    else ++since_best;
  }
  for (size_t i = moves.size(); i > best_len; --i)
    r.apply(moves[i - 1].item, moves[i - 1].from);  // roll back past the peak
  return best_cum;
}

// Combined graph refinement: convergent sweeps always; FM hill-climbing where
// the instance size affords it (same policy as refine_km1, including the
// tiny-instance FM boost).
void refine_cut(const Graph& g, int k, double cap, std::vector<i32>& part,
                int max_passes) {
  CutRefiner r(g, k, cap, part);
  r.sweeps(max_passes);
  if (g.n > 50000) return;
  const int fm_cap = std::min(max_passes, g.n <= 2000 ? 8 : 4);
  for (int pass = 0; pass < fm_cap; ++pass) {
    if (fm_pass(r) <= 0.0f) break;
    r.sweeps(2);
  }
}

// Force balance on the graph side (mirror of rebalance_km1): move vertices
// out of overweight parts into the least-damaging part with room; refine_cut
// afterwards claws quality back.
void rebalance_cut(const Graph& g, int k, double cap, std::vector<i32>& part) {
  std::vector<i64> pw(k, 0);
  for (i32 v = 0; v < g.n; ++v) pw[part[v]] += g.vwgt[v];
  std::vector<float> conn(k);
  for (int pass = 0; pass < 30; ++pass) {
    bool over = false;
    for (int p = 0; p < k; ++p) over |= pw[p] > (i64)cap;
    if (!over) break;
    i64 moves = 0;
    for (i32 v = 0; v < g.n; ++v) {
      int pv = part[v];
      if (pw[pv] <= (i64)cap) continue;
      std::fill(conn.begin(), conn.end(), 0.0f);
      for (i64 e = g.xadj[v]; e < g.xadj[v + 1]; ++e)
        conn[part[g.adj[e]]] += g.wgt[e];
      int best = -1; float best_gain = 0.0f;
      for (int p = 0; p < k; ++p) {
        if (p == pv || pw[p] + g.vwgt[v] > (i64)cap) continue;
        float d = conn[p] - conn[pv];
        if (best == -1 || d > best_gain) { best_gain = d; best = p; }
      }
      if (best != -1) {
        pw[pv] -= g.vwgt[v]; pw[best] += g.vwgt[v];
        part[v] = best; ++moves;
      }
    }
    if (moves == 0) break;
  }
}

i64 edge_cut(const Graph& g, const std::vector<i32>& part) {
  double cut = 0;
  for (i32 v = 0; v < g.n; ++v)
    for (i64 e = g.xadj[v]; e < g.xadj[v + 1]; ++e)
      if (part[v] != part[g.adj[e]]) cut += g.wgt[e];
  return (i64)(cut / 2.0 + 0.5);
}

// ------------------------------------------------------------ multilevel driver
void partition_graph_ml(const Graph& g0, int k, double imbalance, int seed,
                        std::vector<i32>& part) {
  Rng rng((uint64_t)seed);
  std::vector<Graph> levels;
  std::vector<MatchResult> maps;
  levels.push_back(g0);
  const i32 coarse_target = std::max(64, 24 * k);
  while (levels.back().n > coarse_target) {
    MatchResult m = heavy_edge_matching(levels.back(), rng);
    if (m.cn > (i32)(0.97 * levels.back().n)) break;   // matching stalled
    Graph c = contract(levels.back(), m);
    maps.push_back(std::move(m));
    levels.push_back(std::move(c));
  }
  double cap = (1.0 + imbalance) * (double)g0.total_vwgt / k;
  // multi-start at the coarsest level (mirror of the hypergraph driver):
  // several greedy-grow seedings, each refined, keep the best cut
  {
    const Graph& gc = levels.back();
    double coarse_cap = cap * 1.10;     // slack while coarse; finest
                                        // refinement restores the real cap
    i64 best_cut = -1;
    std::vector<i32> best_part;
    const int trials = g0.n <= 2000 ? 16 : 8;   // tiny: search harder
    for (int trial = 0; trial < trials; ++trial) {
      std::vector<i32> cand;
      greedy_grow(gc, k, coarse_cap, cand, rng);
      refine_cut(gc, k, coarse_cap, cand, 10);
      i64 c = edge_cut(gc, cand);
      if (best_cut < 0 || c < best_cut) { best_cut = c; best_part = std::move(cand); }
    }
    part = std::move(best_part);
  }
  // project back up with refinement at each level
  for (int li = (int)levels.size() - 2; li >= 0; --li) {
    const MatchResult& m = maps[li];
    std::vector<i32> fine(levels[li].n);
    for (i32 v = 0; v < levels[li].n; ++v) fine[v] = part[m.cmap[v]];
    part = std::move(fine);
    refine_cut(levels[li], k, cap, part, li == 0 ? 8 : 4);
  }
  rebalance_cut(g0, k, cap, part);
  refine_cut(g0, k, cap, part, 3);
}

// ======================================================= hypergraph (colnet)
struct Hypergraph {
  i32 ncells = 0, nnets = 0;
  std::vector<i64> cellptr;   // cell -> nets
  std::vector<i32> cellnets;
  std::vector<i64> netptr;    // net -> pins(cells)
  std::vector<i32> netpins;
  std::vector<i64> cwgt;      // cell weights
  std::vector<i64> nwgt;      // net weights (identical nets merge, r5)
  i64 total_cwgt = 0;
};

Hypergraph from_cells(i32 ncells, i32 nnets, const i64* cellptr,
                      const i32* cellnets, const i64* cwgt) {
  Hypergraph h;
  h.ncells = ncells; h.nnets = nnets;
  h.cellptr.assign(cellptr, cellptr + ncells + 1);
  h.cellnets.assign(cellnets, cellnets + cellptr[ncells]);
  h.cwgt.assign(ncells, 1);
  if (cwgt) h.cwgt.assign(cwgt, cwgt + ncells);
  h.total_cwgt = std::accumulate(h.cwgt.begin(), h.cwgt.end(), (i64)0);
  h.nwgt.assign(nnets, 1);
  // invert to net -> pins
  h.netptr.assign(nnets + 1, 0);
  for (i64 e = 0; e < (i64)h.cellnets.size(); ++e) h.netptr[h.cellnets[e] + 1]++;
  for (i32 j = 0; j < nnets; ++j) h.netptr[j + 1] += h.netptr[j];
  h.netpins.resize(h.cellnets.size());
  std::vector<i64> pos(h.netptr.begin(), h.netptr.end() - 1);
  for (i32 c = 0; c < ncells; ++c)
    for (i64 e = h.cellptr[c]; e < h.cellptr[c + 1]; ++e)
      h.netpins[pos[h.cellnets[e]]++] = c;
  return h;
}

// Rebuild cell -> nets from net -> pins.  Scanning nets ascending makes each
// cell's list sorted and duplicate-free (each net contributes one entry).
void rebuild_cellnets(Hypergraph& h) {
  h.cellptr.assign(h.ncells + 1, 0);
  for (i32 c : h.netpins) h.cellptr[c + 1]++;
  for (i32 c = 0; c < h.ncells; ++c) h.cellptr[c + 1] += h.cellptr[c];
  h.cellnets.assign(h.netpins.size(), 0);
  std::vector<i64> pos(h.cellptr.begin(), h.cellptr.end() - 1);
  for (i32 j = 0; j < h.nnets; ++j)
    for (i64 p = h.netptr[j]; p < h.netptr[j + 1]; ++p)
      h.cellnets[pos[h.netpins[p]]++] = j;
}

// Net compaction (the PaToH family's identical-net trick, r5 speed pass):
//   * single-pin nets can never be cut (λ ≤ 1 ⇒ km1 contribution 0) — drop;
//   * nets with the SAME pin set contribute identically to km1/gains — merge
//     into one net carrying the summed weight.
// Exact for the weighted km1 objective every consumer below now uses.  The
// payoff compounds through the V-cycle: without it every coarse level drags
// the full fine-level net count through pincounts/km1/greedy scans (measured
// 55-80% of partitioner wall-clock at 0.6-2.45M cells before this change).
void compact_nets(Hypergraph& h) {
  const i32 nn = h.nnets;
  if (h.nwgt.empty()) h.nwgt.assign(nn, 1);
  // hash each net's pin sequence (pins are sorted: netpins is built by
  // scanning cells/nets ascending everywhere in this file)
  std::vector<uint64_t> hash(nn);
  for (i32 j = 0; j < nn; ++j) {
    uint64_t hv = 1469598103934665603ull;
    for (i64 p = h.netptr[j]; p < h.netptr[j + 1]; ++p) {
      hv ^= (uint64_t)(uint32_t)h.netpins[p];
      hv *= 1099511628211ull;
    }
    hash[j] = hv;
  }
  std::unordered_map<uint64_t, std::vector<i32>> groups;
  groups.reserve(nn);
  std::vector<i32> remap(nn, -1);      // old net -> new net (-1 = dropped)
  std::vector<i64> new_nwgt;
  std::vector<i64> new_netptr{0};
  std::vector<i32> new_netpins;
  new_nwgt.reserve(nn);
  i32 nj = 0;
  auto same_pins = [&](i32 a, i32 b) {
    i64 la = h.netptr[a + 1] - h.netptr[a];
    if (la != h.netptr[b + 1] - h.netptr[b]) return false;
    return std::equal(h.netpins.begin() + h.netptr[a],
                      h.netpins.begin() + h.netptr[a + 1],
                      h.netpins.begin() + h.netptr[b]);
  };
  for (i32 j = 0; j < nn; ++j) {
    if (h.netptr[j + 1] - h.netptr[j] < 2) continue;   // single-pin: drop
    auto& bucket = groups[hash[j]];
    i32 found = -1;
    for (i32 rep : bucket)
      if (same_pins(rep, j)) { found = remap[rep]; break; }
    if (found >= 0) {
      new_nwgt[found] += h.nwgt[j];
      remap[j] = found;
      continue;
    }
    bucket.push_back(j);
    remap[j] = nj++;
    new_nwgt.push_back(h.nwgt[j]);
    new_netpins.insert(new_netpins.end(), h.netpins.begin() + h.netptr[j],
                       h.netpins.begin() + h.netptr[j + 1]);
    new_netptr.push_back((i64)new_netpins.size());
  }
  h.nnets = nj;
  h.nwgt = std::move(new_nwgt);
  h.netptr = std::move(new_netptr);
  h.netpins = std::move(new_netpins);
  rebuild_cellnets(h);
}

// heavy-connectivity matching: match cells sharing the most nets
MatchResult hc_matching(const Hypergraph& h, Rng& rng,
                        i64 big_net_threshold) {
  std::vector<i32> order(h.ncells);
  std::iota(order.begin(), order.end(), 0);
  fy_shuffle(order, rng);
  std::vector<i32> match(h.ncells, -1);
  // flat scratch + touched-list instead of a hash map: this loop is the
  // single-core hot path at products scale (2.45M cells × ~2.5k candidate
  // scans), and the array form measured several× faster than unordered_map
  std::vector<i64> shared(h.ncells, 0);
  std::vector<i32> touched;
  touched.reserve(4096);
  // Per-cell candidate-scan budget (r5 speed pass): matching needs a
  // heavy-ish partner, not THE heaviest — capping pin touches bounds the
  // deg² term that dominated coarsening wall-clock at products scale.
  // Per-cell net lists are SORTED by net id (rebuild_cellnets), and net
  // ids follow vertex order, so a plain prefix would systematically favor
  // low-id neighborhoods on id-structured families (BA ages, dcsbm
  // communities) — start the truncated scan at a random rotation instead.
  const i64 scan_budget = 2048;
  for (i32 v : order) {
    if (match[v] != -1) continue;
    i64 budget = scan_budget;
    const i64 vdeg = h.cellptr[v + 1] - h.cellptr[v];
    const i64 rot = vdeg > 0 ? (i64)(rng.next() % (uint64_t)vdeg) : 0;
    for (i64 i = 0; i < vdeg && budget > 0; ++i) {
      const i64 e = h.cellptr[v] + (i + rot) % vdeg;
      i32 net = h.cellnets[e];
      i64 deg = h.netptr[net + 1] - h.netptr[net];
      if (deg > big_net_threshold) continue;        // skip huge nets (cost)
      budget -= deg;
      const i64 w = h.nwgt.empty() ? 1 : h.nwgt[net];
      for (i64 p = h.netptr[net]; p < h.netptr[net + 1]; ++p) {
        i32 u = h.netpins[p];
        if (u != v && match[u] == -1) {
          if (shared[u] == 0) touched.push_back(u);
          shared[u] += w;
        }
      }
    }
    i32 best = -1;
    i64 best_s = 0;
    for (i32 u : touched) {
      if (shared[u] > best_s) { best_s = shared[u]; best = u; }
      shared[u] = 0;
    }
    touched.clear();
    if (best != -1) { match[v] = best; match[best] = v; }
    else match[v] = v;
  }
  MatchResult r;
  r.cmap.assign(h.ncells, -1);
  for (i32 v = 0; v < h.ncells; ++v) {
    if (r.cmap[v] != -1) continue;
    i32 u = match[v];
    r.cmap[v] = r.cn;
    if (u != v && u != -1) r.cmap[u] = r.cn;
    ++r.cn;
  }
  return r;
}

Hypergraph contract_h(const Hypergraph& h, const MatchResult& m) {
  Hypergraph c;
  c.ncells = m.cn; c.nnets = h.nnets;
  c.cwgt.assign(m.cn, 0);
  for (i32 v = 0; v < h.ncells; ++v) c.cwgt[m.cmap[v]] += h.cwgt[v];
  c.total_cwgt = h.total_cwgt;
  // coarse cell -> dedup'd union of nets
  std::vector<std::vector<i32>> nets(m.cn);
  for (i32 v = 0; v < h.ncells; ++v) {
    auto& dst = nets[m.cmap[v]];
    for (i64 e = h.cellptr[v]; e < h.cellptr[v + 1]; ++e)
      dst.push_back(h.cellnets[e]);
  }
  c.cellptr.assign(m.cn + 1, 0);
  for (i32 cv = 0; cv < m.cn; ++cv) {
    auto& d = nets[cv];
    std::sort(d.begin(), d.end());
    d.erase(std::unique(d.begin(), d.end()), d.end());
    c.cellptr[cv + 1] = c.cellptr[cv] + (i64)d.size();
  }
  c.cellnets.reserve(c.cellptr[m.cn]);
  for (i32 cv = 0; cv < m.cn; ++cv)
    c.cellnets.insert(c.cellnets.end(), nets[cv].begin(), nets[cv].end());
  c.nwgt = h.nwgt.empty() ? std::vector<i64>(h.nnets, 1) : h.nwgt;
  // rebuild net -> pins, then compact: dropping now-single-pin nets and
  // merging now-identical ones is what keeps coarse levels from dragging
  // the fine level's full net count through every pincount/gain scan
  c.netptr.assign(c.nnets + 1, 0);
  for (i32 x : c.cellnets) c.netptr[x + 1]++;
  for (i32 j = 0; j < c.nnets; ++j) c.netptr[j + 1] += c.netptr[j];
  c.netpins.resize(c.cellnets.size());
  std::vector<i64> pos(c.netptr.begin(), c.netptr.end() - 1);
  for (i32 cv = 0; cv < m.cn; ++cv)
    for (i64 e = c.cellptr[cv]; e < c.cellptr[cv + 1]; ++e)
      c.netpins[pos[c.cellnets[e]]++] = cv;
  compact_nets(c);
  return c;
}

// km1 objective helpers: per-net pin counts per part (dense nnets × k)
struct PinCounts {
  std::vector<i32> cnt;   // nnets * k
  int k;
  i32* row(i32 net) { return cnt.data() + (i64)net * k; }
};

i64 km1_total(const Hypergraph& h, PinCounts& pc) {
  i64 s = 0;
  for (i32 j = 0; j < h.nnets; ++j) {
    i32* r = pc.row(j);
    int lambda = 0;
    for (int p = 0; p < pc.k; ++p) lambda += r[p] > 0;
    if (lambda > 1)
      s += (h.nwgt.empty() ? 1 : h.nwgt[j]) * (i64)(lambda - 1);
  }
  return s;
}

void build_pincounts(const Hypergraph& h, const std::vector<i32>& part,
                     PinCounts& pc) {
  pc.cnt.assign((i64)h.nnets * pc.k, 0);
  for (i32 j = 0; j < h.nnets; ++j) {
    i32* r = pc.row(j);
    for (i64 p = h.netptr[j]; p < h.netptr[j + 1]; ++p) r[part[h.netpins[p]]]++;
  }
}

// Connectivity-aware greedy placement on the coarsest hypergraph: cells are
// placed in random order into the part their nets already touch most
// (constructive form of the km1 gain).  Two placement disciplines, chosen
// per multi-start trial for diversity:
//   prefer_target=false — any cap-feasible part (best when the cap binds:
//     communities fill their part to the brim before spilling);
//   prefer_target=true — parts still under the ideal weight total/k first
//     (best when the cap is loose: stops early parts swallowing whole
//     neighborhoods and starving the rest).
void greedy_grow_h(const Hypergraph& h, int k, double cap,
                   std::vector<i32>& part, Rng& rng,
                   bool prefer_target) {
  part.assign(h.ncells, -1);
  std::vector<i32> order(h.ncells);
  std::iota(order.begin(), order.end(), 0);
  fy_shuffle(order, rng);
  std::vector<i64> pw(k, 0);
  const i64 target = h.total_cwgt / k;
  // net -> set of parts present, tracked as dense counts
  std::vector<i32> netpart((i64)h.nnets * k, 0);
  std::vector<i64> affinity(k);
  for (i32 idx = 0; idx < h.ncells; ++idx) {
    i32 v = order[idx];
    std::fill(affinity.begin(), affinity.end(), 0);
    for (i64 e = h.cellptr[v]; e < h.cellptr[v + 1]; ++e) {
      const i32 net = h.cellnets[e];
      const i64 w = h.nwgt.empty() ? 1 : h.nwgt[net];
      const i32* r = netpart.data() + (i64)net * k;
      for (int p = 0; p < k; ++p) affinity[p] += (r[p] > 0) * w;
    }
    int best = -1; i64 best_a = -1;
    if (prefer_target)
      for (int p = 0; p < k; ++p)   // first choice: parts still under target
        if (pw[p] + h.cwgt[v] <= target && affinity[p] > best_a) {
          best_a = affinity[p]; best = p;
        }
    if (best == -1)
      for (int p = 0; p < k; ++p)   // anywhere the cap allows
        if (pw[p] + h.cwgt[v] <= (i64)cap && affinity[p] > best_a) {
          best_a = affinity[p]; best = p;
        }
    if (best == -1)   // everything full (rounding): lightest part
      best = (int)(std::min_element(pw.begin(), pw.end()) - pw.begin());
    part[v] = best; pw[best] += h.cwgt[v];
    for (i64 e = h.cellptr[v]; e < h.cellptr[v + 1]; ++e)
      netpart[(i64)h.cellnets[e] * k + best]++;
  }
}

// km1 refinement state shared by the sweep and FM phases below.
struct Km1Refiner {
  const Hypergraph& h;
  const int k;
  const double cap;
  std::vector<i32>& part;
  PinCounts pc;
  std::vector<i64> pw;
  std::vector<i64> cnt;     // scratch: net weight of v present in part p
  std::vector<char> cut;    // per net: pins in >= 2 parts (λ >= 2)

  Km1Refiner(const Hypergraph& h_, int k_, double cap_, std::vector<i32>& part_)
      : h(h_), k(k_), cap(cap_), part(part_), cnt(k_) {
    pc.k = k;
    build_pincounts(h, part, pc);
    pw.assign(k, 0);
    for (i32 v = 0; v < h.ncells; ++v) pw[part[v]] += h.cwgt[v];
    cut.assign(h.nnets, 0);
    for (i32 j = 0; j < h.nnets; ++j) {
      const i32* r = pc.row(j);
      int lambda = 0;
      for (int p = 0; p < k && lambda < 2; ++p) lambda += r[p] > 0;
      cut[j] = lambda >= 2;
    }
  }

  i64 netw(i32 j) const { return h.nwgt.empty() ? 1 : h.nwgt[j]; }

  // Best feasible move for v.  Weighted km1 gain of moving v from pv to p:
  //   + weight of every net where v is pv's last pin (leaving removes pv)
  //   - weight of every net where p has no pin yet (arriving adds p)
  //   = leave_bonus - (degw(v) - weight of v's nets where p already present).
  // Ties prefer the lighter target part.  target = -1 when v is interior or
  // no part has room.  Interior test first: a cell none of whose nets are
  // cut sees every pin in pv — deg work instead of deg·k (the r5 sweep
  // early-out; at products scale most cells are interior once the
  // partition settles, and the full-gain fall-through is exactly the old
  // code, so results are unchanged).
  i64 best_move(i32 v, i32& target) {
    const int pv = part[v];
    bool anycut = false;
    for (i64 e = h.cellptr[v]; e < h.cellptr[v + 1]; ++e)
      if (cut[h.cellnets[e]]) { anycut = true; break; }
    if (!anycut) { target = -1; return 0; }
    std::fill(cnt.begin(), cnt.end(), 0);
    i64 leave_bonus = 0, degw = 0;
    for (i64 e = h.cellptr[v]; e < h.cellptr[v + 1]; ++e) {
      const i32 net = h.cellnets[e];
      const i64 w = netw(net);
      degw += w;
      const i32* r = pc.row(net);
      if (r[pv] == 1) leave_bonus += w;
      for (int p = 0; p < k; ++p)
        if (p != pv && r[p] > 0) cnt[p] += w;
    }
    target = -1;
    i64 best_gain = 0;
    for (int p = 0; p < k; ++p) {
      if (p == pv) continue;
      if (pw[p] + h.cwgt[v] > (i64)cap) continue;
      i64 gn = leave_bonus - (degw - cnt[p]);
      if (target == -1 || gn > best_gain ||
          (gn == best_gain && pw[p] < pw[target])) {
        best_gain = gn; target = p;
      }
    }
    return target == -1 ? 0 : best_gain;
  }

  void apply(i32 v, i32 to) {
    const int pv = part[v];
    for (i64 e = h.cellptr[v]; e < h.cellptr[v + 1]; ++e) {
      const i32 net = h.cellnets[e];
      i32* r = pc.row(net);
      r[pv]--; r[to]++;
      // λ can only change through the touched parts; recount lazily
      int lambda = 0;
      for (int p = 0; p < k && lambda < 2; ++p) lambda += r[p] > 0;
      cut[net] = lambda >= 2;
    }
    pw[pv] -= h.cwgt[v]; pw[to] += h.cwgt[v];
    part[v] = to;
  }

  using Gain = i64;
  i32 n_items() const { return h.ncells; }

  // Greedy boundary sweeps: linear-time passes applying only positive-gain
  // moves in cell order; converge fast and carry the bulk of refinement at
  // every scale.  Hill-climbing is the shared fm_pass() above.
  void sweeps(int max_passes) {
    for (int pass = 0; pass < max_passes; ++pass) {
      i64 moves = 0;
      for (i32 v = 0; v < h.ncells; ++v) {
        i32 t; i64 g = best_move(v, t);
        if (t >= 0 && g > 0) { apply(v, t); ++moves; }
      }
      if (moves == 0) break;
    }
  }
};

// Combined refinement: fast convergent sweeps always; FM hill-climbing where
// the instance size affords it, with sweeps mopping up after each FM gain.
void refine_km1(const Hypergraph& h, int k, double cap, std::vector<i32>& part,
                int max_passes) {
  Km1Refiner r(h, k, cap, part);
  r.sweeps(max_passes);
  if (h.ncells > 50000) return;
  const int fm_cap = std::min(max_passes, h.ncells <= 2000 ? 8 : 4);
  for (int pass = 0; pass < fm_cap; ++pass) {
    if (fm_pass(r) <= 0) break;
    r.sweeps(2);
  }
}

// Force balance: move cells out of overweight parts into the least-damaging
// part with room (gain may be negative — feasibility first, then refine_km1
// claws quality back).
void rebalance_km1(const Hypergraph& h, int k, double cap,
                   std::vector<i32>& part) {
  PinCounts pc; pc.k = k;
  build_pincounts(h, part, pc);
  std::vector<i64> pw(k, 0);
  for (i32 v = 0; v < h.ncells; ++v) pw[part[v]] += h.cwgt[v];
  std::vector<i64> gain(k);
  for (int pass = 0; pass < 30; ++pass) {
    bool over = false;
    for (int p = 0; p < k; ++p) over |= pw[p] > (i64)cap;
    if (!over) break;
    i64 moves = 0;
    for (i32 v = 0; v < h.ncells; ++v) {
      int pv = part[v];
      if (pw[pv] <= (i64)cap) continue;
      std::fill(gain.begin(), gain.end(), 0);
      i64 leave_bonus = 0, degw = 0;
      for (i64 e = h.cellptr[v]; e < h.cellptr[v + 1]; ++e) {
        const i32 net = h.cellnets[e];
        const i64 w = h.nwgt.empty() ? 1 : h.nwgt[net];
        degw += w;
        i32* r = pc.row(net);
        if (r[pv] == 1) leave_bonus += w;
        for (int p = 0; p < k; ++p)
          if (p != pv && r[p] > 0) gain[p] += w;
      }
      int best = -1; i64 best_gain = 0;
      for (int p = 0; p < k; ++p) {
        if (p == pv || pw[p] + h.cwgt[v] > (i64)cap) continue;
        i64 gn = leave_bonus - (degw - gain[p]);
        if (best == -1 || gn > best_gain) { best_gain = gn; best = p; }
      }
      if (best != -1) {
        for (i64 e = h.cellptr[v]; e < h.cellptr[v + 1]; ++e) {
          i32* r = pc.row(h.cellnets[e]);
          r[pv]--; r[best]++;
        }
        pw[pv] -= h.cwgt[v]; pw[best] += h.cwgt[v];
        part[v] = best; ++moves;
      }
    }
    if (moves == 0) break;
  }
}

void partition_hypergraph_ml(const Hypergraph& h0, int k, double imbalance,
                             int seed, std::vector<i32>& part) {
  const bool timing = std::getenv("SGCN_TIMING") != nullptr;
  auto now = [] { return std::chrono::steady_clock::now(); };
  auto secs = [](auto a, auto b) {
    return std::chrono::duration<double>(b - a).count();
  };
  auto t0 = now();
  Rng rng((uint64_t)seed);
  std::vector<Hypergraph> levels;
  std::vector<MatchResult> maps;
  levels.push_back(h0);
  // compact the working copy of the finest level too: a column-net
  // hypergraph of an undirected graph has every net duplicated against its
  // mirror, so identical-net merging halves even level-0 gain scans, and
  // the weighted objective it produces is exactly the original km1
  compact_nets(levels[0]);
  const i32 coarse_target = std::max(64, 24 * k);
  // skip nets with more pins than this during matching (cost control)
  while (levels.back().ncells > coarse_target) {
    const Hypergraph& cur = levels.back();
    i64 avg_deg = cur.netpins.empty() ? 1 :
        std::max<i64>(2, (i64)cur.netpins.size() / std::max(1, cur.nnets));
    MatchResult m = hc_matching(cur, rng, 8 * avg_deg);
    if (m.cn > (i32)(0.97 * cur.ncells)) break;
    Hypergraph c = contract_h(cur, m);
    maps.push_back(std::move(m));
    levels.push_back(std::move(c));
  }
  if (timing)
    std::fprintf(stderr,
                 "[sgcnpart] coarsen: %.2fs levels=%zu coarsest=%d "
                 "(nets=%d pins=%zu)\n",
                 secs(t0, now()), levels.size(), levels.back().ncells,
                 levels.back().nnets, levels.back().netpins.size());
  double cap = (1.0 + imbalance) * (double)h0.total_cwgt / k;
  // multi-start at the coarsest level: keep the best refined candidate
  {
    const Hypergraph& hc = levels.back();
    double coarse_cap = cap * 1.10;     // extra slack while coarse; finest
                                        // refinement restores the real cap
    i64 best_km1 = -1;
    std::vector<i32> best_part;
    PinCounts pc; pc.k = k;
    // Column-net hypergraphs keep O(original pins / ~20) pins at the
    // coarsest level (nets rarely become identical), so a coarse trial is
    // O(pins·k·passes), NOT O(coarse cells) — budget the multistart by
    // pins (r5 speed pass; at products scale 8 full trials were ~15% of
    // total wall-clock for marginal quality: uncoarsening sweeps do the
    // bulk of refinement anyway).
    int trials = h0.ncells <= 2000 ? 16 : 8;
    const i64 pins = (i64)hc.netpins.size();
    if (pins > 2'000'000)
      trials = std::max<int>(3, (int)(8 * 2'000'000 / pins));
    for (int trial = 0; trial < trials; ++trial) {
      auto tg = now();
      std::vector<i32> cand;
      greedy_grow_h(hc, k, coarse_cap, cand, rng, trial % 2 == 1);
      auto tr_ = now();
      refine_km1(hc, k, coarse_cap, cand, 8);
      build_pincounts(hc, cand, pc);
      i64 score = km1_total(hc, pc);
      if (timing)
        std::fprintf(stderr,
                     "[sgcnpart]   trial %d: grow=%.2fs refine=%.2fs "
                     "km1=%lld\n", trial, secs(tg, tr_), secs(tr_, now()),
                     (long long)score);
      if (best_km1 < 0 || score < best_km1) {
        best_km1 = score; best_part = std::move(cand);
      }
    }
    part = std::move(best_part);
  }
  if (timing)
    std::fprintf(stderr, "[sgcnpart] coarse multistart: %.2fs\n", secs(t0, now()));
  for (int li = (int)levels.size() - 2; li >= 0; --li) {
    auto tl = now();
    const MatchResult& m = maps[li];
    std::vector<i32> fine(levels[li].ncells);
    for (i32 v = 0; v < levels[li].ncells; ++v) fine[v] = part[m.cmap[v]];
    part = std::move(fine);
    refine_km1(levels[li], k, cap, part, li == 0 ? 6 : 3);
    if (timing)
      std::fprintf(stderr, "[sgcnpart] level %d (n=%d): %.2fs\n", li,
                   levels[li].ncells, secs(tl, now()));
  }
  auto tr = now();
  rebalance_km1(levels[0], k, cap, part);
  refine_km1(levels[0], k, cap, part, 3);
  if (timing)
    std::fprintf(stderr, "[sgcnpart] rebalance+final: %.2fs total=%.2fs\n",
                 secs(tr, now()), secs(t0, now()));
}

// ---------------------------------------------------- recursive bisection
// Direct k-way km1 refinement costs O(deg·k) per move, which at k >= 32 and
// products scale made hp both slow (5 700 s) and ~3% WORSE than gp
// (round-5 k-sweep, bench_artifacts/products_ksweep.json).  Recursive bisection — the PaToH/hMETIS
// production strategy — eliminates the k factor: log2(k) levels of 2-way
// partitions, each with the full multilevel machinery at k=2.
//
// The km1 objective decomposes EXACTLY over a bisection with net
// splitting: for a net with pins on both sides, λ over the final k parts
// equals λ_left + λ_right (its sub-nets' part counts), so
//   km1(net) = λ−1 = (λ_left−1) + (λ_right−1) + 1,
// i.e. total km1 = (top-level cut nets) + Σ_side km1(side sub-hypergraph)
// where each side keeps the net restricted to its own pins.  Minimizing
// the 2-way cut then recursing on split nets IS minimizing km1.
// Per-level imbalance halves (ε/2 each level) so the final parts respect
// the caller's cap.  Power-of-two k only (even splits); other k use the
// direct k-way driver.
void partition_hypergraph_rb(const Hypergraph& h, int k, double imbalance,
                             int seed, std::vector<i32>& part) {
  if (k == 1) { part.assign(h.ncells, 0); return; }
  // split the imbalance budget GEOMETRICALLY over the remaining levels:
  // (1+ε_level)^levels == 1+ε exactly, so the final parts respect the
  // caller's cap without the additive-halving scheme's two failure modes
  // (deep levels starved below one cell of slack — refinement frozen —
  // and compounded overshoot at large ε).  The per-level slack is floored
  // at one max cell weight so a feasible move always exists.
  const int levels = [] (int kk) {
    int l = 0; while (kk > 1) { kk >>= 1; ++l; } return l; } (k);
  double eps_level = std::pow(1.0 + imbalance, 1.0 / levels) - 1.0;
  const i64 max_cw = h.cwgt.empty() ? 1 :
      *std::max_element(h.cwgt.begin(), h.cwgt.end());
  if (h.total_cwgt > 0)
    eps_level = std::max(eps_level, 2.0 * (double)max_cw / h.total_cwgt);
  std::vector<i32> top;
  // the k==2 base case gets the level budget like any other level (the
  // recursion has already consumed the rest of ε above it; when called
  // directly with k==2, levels==1 makes eps_level == imbalance)
  partition_hypergraph_ml(h, 2, eps_level, seed, top);
  if (k == 2) { part = top; return; }
  const double eps_rem =
      std::pow(1.0 + imbalance, (levels - 1.0) / levels) - 1.0;
  part.assign(h.ncells, -1);
  for (int side = 0; side < 2; ++side) {
    // extract the side's sub-hypergraph: cells of this side, nets
    // restricted to their pins on this side (< 2 pins -> dropped, they
    // can no longer be cut), weights carried
    std::vector<i32> cells;                    // sub id -> parent id
    std::vector<i32> sub_of(h.ncells, -1);
    for (i32 v = 0; v < h.ncells; ++v)
      if (top[v] == side) {
        sub_of[v] = (i32)cells.size();
        cells.push_back(v);
      }
    Hypergraph s;
    s.ncells = (i32)cells.size();
    s.cwgt.resize(s.ncells);
    for (i32 sv = 0; sv < s.ncells; ++sv) s.cwgt[sv] = h.cwgt[cells[sv]];
    s.total_cwgt = std::accumulate(s.cwgt.begin(), s.cwgt.end(), (i64)0);
    s.netptr.push_back(0);
    for (i32 j = 0; j < h.nnets; ++j) {
      i64 kept = 0;
      for (i64 p = h.netptr[j]; p < h.netptr[j + 1]; ++p)
        if (sub_of[h.netpins[p]] >= 0) {
          s.netpins.push_back(sub_of[h.netpins[p]]);
          ++kept;
        }
      if (kept < 2) {
        s.netpins.resize(s.netpins.size() - kept);   // drop
      } else {
        s.netptr.push_back((i64)s.netpins.size());
        s.nwgt.push_back(h.nwgt.empty() ? 1 : h.nwgt[j]);
      }
    }
    s.nnets = (i32)s.nwgt.size();
    rebuild_cellnets(s);
    std::vector<i32> sub_part;
    partition_hypergraph_rb(s, k / 2, eps_rem, seed + 104729 + side,
                            sub_part);
    const int off = side * (k / 2);
    for (i32 sv = 0; sv < s.ncells; ++sv)
      part[cells[sv]] = off + sub_part[sv];
  }
}

// Restart budget: whole-multilevel restarts are the "more V-cycles" quality
// lever, but they scale linearly in the instance size, so the budget is
// size-capped (the round-3 scale path: one restart at products scale keeps
// the 2.45M-cell run inside a single-core time budget).  SGCN_RESTARTS
// overrides for experiments.
int restart_budget(i64 n) {
  if (const char* env = std::getenv("SGCN_RESTARTS")) {
    int r = std::atoi(env);
    if (r > 0) return r;
  }
  return n <= 2000 ? 12 : n <= 20000 ? 6 : n <= 1000000 ? 3 : 1;
}

// The k>1 body of sgcn_partition_hypergraph, extracted so the cache-aware
// entry point (sgcn_partition_hypergraph_cache) reuses the identical
// driver: restarts, RB-vs-direct selection, post-RB polish, and the
// graph-seeded portfolio — byte-for-byte the behavior the plain ABI had.
void hypergraph_driver(const Hypergraph& h, int k, double imbalance,
                       int seed, std::vector<i32>& part) {
  const i32 ncells = h.ncells;
  const i32 nnets = h.nnets;
  // restarts of the whole multilevel procedure (different coarsening and
  // seeding draws); keep the best final km1 — the "more V-cycles /
  // restarts" quality lever of the PaToH quality preset.  Small instances
  // are cheap enough to search harder; huge ones get one pass.
  const int restarts = restart_budget(ncells);
  i64 best = -1;
  std::vector<i32> cand;
  PinCounts pc;
  pc.k = k;
  // high power-of-two k: recursive bisection (see
  // partition_hypergraph_rb) replaces the direct k-way driver, whose
  // O(deg·k) refinement measured slower AND worse at k >= 32;
  // SGCN_HP_RB=1 forces RB wherever k is a power of two, =0 disables
  const char* rb_env = std::getenv("SGCN_HP_RB");
  const bool pow2 = (k & (k - 1)) == 0;
  const bool use_rb = pow2 && rb_env != nullptr ? rb_env[0] == '1'
                      : pow2 && k >= 32;
  // Post-RB polish runs on a compact_nets'd COPY of the fine hypergraph
  // (ADVICE r5): a column-net hypergraph of an undirected graph carries
  // every net twice (mirror pairs), so identical-net merging halves the
  // O(deg·k) gain scans of the direct k-way passes while the weighted
  // km1 objective — and therefore every move decision's gain — stays
  // exactly the original km1 (the ml path already refines compacted
  // levels for the same reason).  Built once, reused across restarts;
  // cells are untouched by compaction, so the part vector carries over.
  Hypergraph hpol;
  if (use_rb) {
    hpol = h;
    compact_nets(hpol);
  }
  for (int r = 0; r < restarts; ++r) {
    if (use_rb)
      partition_hypergraph_rb(h, k, imbalance, seed + 7919 * r, cand);
    else
      partition_hypergraph_ml(h, k, imbalance, seed + 7919 * r, cand);
    double cap = (1.0 + imbalance) * (double)h.total_cwgt / k;
    if (use_rb) {
      // one direct k-way polish pass: RB never saw cross-side moves
      rebalance_km1(hpol, k, cap, cand);
      refine_km1(hpol, k, cap, cand, 2);
    }
    build_pincounts(h, cand, pc);
    i64 score = km1_total(h, pc);
    if (best < 0 || score < best) { best = score; part = cand; }
  }
  // Portfolio restart (small square instances): seed from the graph-model
  // (edge-cut) partitioner's basin and refine under km1.  On small
  // near-symmetric matrices the graph search sometimes finds a better
  // basin than column-net coarsening; km1 refinement keeps the
  // connectivity objective in charge, so the hypergraph partitioner never
  // loses to the graph one on its own metric.  Gated by size so the
  // products-scale run stays lean (hp wins outright there anyway,
  // bench_artifacts/partition_comm_sweep.json).
  if (ncells == nnets && ncells <= 200000) {
    Graph g;
    g.n = ncells;
    std::vector<i64> keys;
    keys.reserve(2 * h.cellnets.size());
    for (i32 c = 0; c < ncells; ++c)
      for (i64 e = h.cellptr[c]; e < h.cellptr[c + 1]; ++e) {
        i64 j = h.cellnets[e];
        if (j == c) continue;
        keys.push_back((i64)c * nnets + j);
        keys.push_back(j * (i64)nnets + c);
      }
    std::sort(keys.begin(), keys.end());
    keys.erase(std::unique(keys.begin(), keys.end()), keys.end());
    g.xadj.assign(ncells + 1, 0);
    g.adj.resize(keys.size());
    g.wgt.assign(keys.size(), 1.0f);
    for (i64 key : keys) g.xadj[key / nnets + 1]++;
    for (i32 v = 0; v < ncells; ++v) g.xadj[v + 1] += g.xadj[v];
    for (size_t e = 0; e < keys.size(); ++e)
      g.adj[e] = (i32)(keys[e] % nnets);
    g.vwgt = h.cwgt;                 // balance on cell weights carries over
    g.total_vwgt = h.total_cwgt;
    double cap = (1.0 + imbalance) * (double)h.total_cwgt / k;
    // same restart budget as the standalone graph partitioner, but each
    // candidate is scored on km1 after connectivity refinement
    for (int r = 0; r < restarts; ++r) {
      partition_graph_ml(g, k, imbalance, seed + 31337 + 7919 * r, cand);
      rebalance_km1(h, k, cap, cand);
      refine_km1(h, k, cap, cand, 6);
      build_pincounts(h, cand, pc);
      i64 score = km1_total(h, pc);
      if (score < best) { best = score; part = cand; }
    }
  }
}

// ------------------------------------------------- cache-aware km1 (replicas)
// Hot-halo replication (CaPGNN-style): the training system promotes the
// top-B boundary rows to persistent replicas on their consumer chips, so a
// net whose source vertex is replicated STOPS costing km1 — its rows ship
// once per refresh instead of once per exchange.  The cache-aware objective
// of a partition is therefore km1 minus the contribution of the best-B
// replica candidates, where candidates are ranked by (λ−1)·pins — the
// hypergraph face of the plan-time λ·degree ranking (the owner part is a
// pin here, so plan-λ = λ−1; pins ≈ consumer edges).  Deterministic
// tie-break on net id, matching the plan side's vertex-id tie-break.
struct CacheObjective {
  i64 obj = 0;                 // km1 with the selected nets' cost removed
  std::vector<i32> nets;       // the selected (replicated) nets
};

CacheObjective cache_objective(const Hypergraph& h,
                               const std::vector<i32>& part, int k,
                               i32 budget) {
  PinCounts pc;
  pc.k = k;
  build_pincounts(h, part, pc);
  CacheObjective out;
  std::vector<std::pair<i64, i32>> scored;   // (-score, net): top-B order
  std::vector<i64> contrib(h.nnets, 0);
  for (i32 j = 0; j < h.nnets; ++j) {
    const i32* r = pc.row(j);
    int lambda = 0;
    for (int p = 0; p < k; ++p) lambda += r[p] > 0;
    if (lambda < 2) continue;
    const i64 w = h.nwgt.empty() ? 1 : h.nwgt[j];
    contrib[j] = w * (i64)(lambda - 1);
    out.obj += contrib[j];
    const i64 pins = h.netptr[j + 1] - h.netptr[j];
    scored.push_back({-((i64)(lambda - 1) * pins), j});
  }
  const i32 b = (i32)std::min<i64>(budget, (i64)scored.size());
  std::partial_sort(scored.begin(), scored.begin() + b, scored.end());
  out.nets.reserve(b);
  for (i32 i = 0; i < b; ++i) {
    out.obj -= contrib[scored[i].second];
    out.nets.push_back(scored[i].second);
  }
  return out;
}

// Co-optimize a partition with the replica budget: alternate (a) select the
// current top-B replica nets, (b) refine under a weight vector with those
// nets ZEROED (their pins move freely — the cut stops fighting the cache),
// (c) re-score with a FRESH selection.  The incoming partition is the
// first candidate, so the result's cache objective is <= the cache-blind
// driver's by construction (monotone best-keep).
i64 cache_cooptimize(const Hypergraph& h, int k, double imbalance,
                     i32 budget, std::vector<i32>& part) {
  const double cap = (1.0 + imbalance) * (double)h.total_cwgt / k;
  Hypergraph hz = h;
  if (hz.nwgt.empty()) hz.nwgt.assign(h.nnets, 1);
  std::vector<i32> best = part;
  i64 best_obj = cache_objective(h, part, k, budget).obj;
  for (int round = 0; round < 3; ++round) {
    CacheObjective sel = cache_objective(h, part, k, budget);
    std::vector<i64> saved;
    saved.reserve(sel.nets.size());
    for (i32 j : sel.nets) {
      saved.push_back(hz.nwgt[j]);
      hz.nwgt[j] = 0;
    }
    rebalance_km1(hz, k, cap, part);
    refine_km1(hz, k, cap, part, 3);
    for (size_t i = 0; i < sel.nets.size(); ++i)
      hz.nwgt[sel.nets[i]] = saved[i];
    const i64 obj = cache_objective(h, part, k, budget).obj;
    if (obj < best_obj) {
      best_obj = obj;
      best = part;
    }
  }
  part = std::move(best);
  return best_obj;
}

}  // namespace

// ===================================================================== C ABI
extern "C" {

// Multilevel k-way graph partition, edge-cut objective.
// xadj[n+1], adjncy/adjwgt[xadj[n]], vwgt[n] (nullable -> 1s).
// Returns 0 on success; part_out[n], edgecut_out optional.
int sgcn_partition_graph(i32 n, const i64* xadj, const i32* adjncy,
                         const float* adjwgt, const i64* vwgt, int k,
                         double imbalance, int seed, i32* part_out,
                         i64* edgecut_out) {
  if (n <= 0 || k <= 0) return 1;
  Graph g;
  g.n = n;
  g.xadj.assign(xadj, xadj + n + 1);
  g.adj.assign(adjncy, adjncy + xadj[n]);
  if (adjwgt) g.wgt.assign(adjwgt, adjwgt + xadj[n]);
  else g.wgt.assign(xadj[n], 1.0f);
  if (vwgt) g.vwgt.assign(vwgt, vwgt + n);
  else g.vwgt.assign(n, 1);
  g.total_vwgt = std::accumulate(g.vwgt.begin(), g.vwgt.end(), (i64)0);
  std::vector<i32> part;
  if (k == 1) part.assign(n, 0);
  else {
    // multilevel restarts, best final cut kept (same policy as the
    // hypergraph side; closes the round-3 gp-vs-hp quality gap)
    const int restarts = restart_budget(n);
    i64 best = -1;
    std::vector<i32> cand;
    for (int r = 0; r < restarts; ++r) {
      partition_graph_ml(g, k, imbalance, seed + 7919 * r, cand);
      i64 score = edge_cut(g, cand);
      if (best < 0 || score < best) { best = score; part = cand; }
    }
  }
  std::copy(part.begin(), part.end(), part_out);
  if (edgecut_out) *edgecut_out = edge_cut(g, part);
  return 0;
}

// Multilevel column-net hypergraph partition, connectivity-1 (km1) objective.
// cells 0..ncells-1 with cellptr/cellnets adjacency into nets 0..nnets-1;
// cwgt nullable (-> 1s). part_out[ncells], km1_out optional.
int sgcn_partition_hypergraph(i32 ncells, i32 nnets, const i64* cellptr,
                              const i32* cellnets, const i64* cwgt, int k,
                              double imbalance, int seed, i32* part_out,
                              i64* km1_out) {
  if (ncells <= 0 || k <= 0) return 1;
  Hypergraph h = from_cells(ncells, nnets, cellptr, cellnets, cwgt);
  std::vector<i32> part;
  if (k == 1) part.assign(ncells, 0);
  else hypergraph_driver(h, k, imbalance, seed, part);
  std::copy(part.begin(), part.end(), part_out);
  if (km1_out) {
    PinCounts pc; pc.k = k;
    build_pincounts(h, part, pc);
    *km1_out = km1_total(h, pc);
  }
  return 0;
}

// Cache-aware flavor (hot-halo replication, docs/replication.md): same
// driver, then co-optimize the cut with the replica budget — a net whose
// source vertex is replicated costs 0, so refinement under the zeroed
// weights moves pins the cache already pays for.  ``km1_cache_out`` gets
// the cache-aware objective (km1 minus the selected top-B nets'
// contribution, selection by (λ−1)·pins); by construction it is <= the
// same objective evaluated on the cache-blind driver's partition at the
// same seed/balance (the blind partition is the first candidate kept).
// ``replica_budget <= 0`` degenerates to the plain driver with
// km1_cache_out == km1_out.
int sgcn_partition_hypergraph_cache(i32 ncells, i32 nnets,
                                    const i64* cellptr, const i32* cellnets,
                                    const i64* cwgt, int k, double imbalance,
                                    int seed, i32 replica_budget,
                                    i32* part_out, i64* km1_out,
                                    i64* km1_cache_out) {
  if (ncells <= 0 || k <= 0) return 1;
  Hypergraph h = from_cells(ncells, nnets, cellptr, cellnets, cwgt);
  std::vector<i32> part;
  i64 cache = 0;
  if (k == 1) part.assign(ncells, 0);
  else {
    hypergraph_driver(h, k, imbalance, seed, part);
    if (replica_budget > 0)
      cache = cache_cooptimize(h, k, imbalance, replica_budget, part);
  }
  std::copy(part.begin(), part.end(), part_out);
  i64 km1 = 0;
  {
    PinCounts pc; pc.k = k;
    build_pincounts(h, part, pc);
    km1 = km1_total(h, pc);
  }
  if (k > 1 && replica_budget <= 0)
    cache = km1;
  if (km1_out) *km1_out = km1;
  if (km1_cache_out) *km1_cache_out = cache;
  return 0;
}

// Buffer-scanning MatrixMarket coordinate reader used by the native CLI
// (role of the reference's C readers, Parallel-GCN/main.c:609-648,
// GCN-HP/main.cpp:366-405).  NOTE the Python path (sgcn_tpu/io/mtx.py) uses
// scipy's multithreaded fast_matrix_market parser, which measured faster
// than this single-threaded scanner — this exists so `sgcnpart` has no
// Python dependency, not as the Python loader.
// Line-aware: comments allowed anywhere, extra per-line tokens (e.g. the
// imaginary part of complex files) ignored.  Symmetric/skew storage
// expanded, pattern values = 1.0.  Outputs malloc'd arrays owned by the
// caller (release with sgcn_free).  Returns 0 ok, 1 io error, 2 malformed,
// 3 out of memory.
int sgcn_read_mtx(const char* path, i64* nrows_out, i64* ncols_out,
                  i64* nnz_out, i32** row_out, i32** col_out,
                  float** val_out) {
  FILE* f = std::fopen(path, "rb");
  if (!f) return 1;
  // 64-bit size probe: long is 32-bit on LLP64, so >2 GiB files would
  // overflow a plain ftell there; read in chunks until EOF instead.
  std::vector<char> buf;
  {
#if defined(_WIN32)
    if (_fseeki64(f, 0, SEEK_END) == 0) {
      long long sz = _ftelli64(f);
#else
    if (fseeko(f, 0, SEEK_END) == 0) {
      off_t sz = ftello(f);
#endif
      if (sz > 0) buf.reserve((size_t)sz + 1);   // one allocation, no 2x peak
    }
    std::rewind(f);
    std::vector<char> chunk(1 << 20);   // heap: callers may run on small stacks
    size_t got;
    while ((got = std::fread(chunk.data(), 1, chunk.size(), f)) > 0)
      buf.insert(buf.end(), chunk.data(), chunk.data() + got);
    if (std::ferror(f)) { std::fclose(f); return 1; }
  }
  std::fclose(f);
  const size_t fsize = buf.size();
  buf.push_back('\0');

  const char* p = buf.data();
  const char* end = p + fsize;
  bool symmetric = false, skew = false, pattern = false;
  bool header_done = false;
  long long nr = 0, nc = 0, declared = 0;
  size_t cap = 0, nnz = 0;
  i32* rows = nullptr;
  i32* cols = nullptr;
  float* vals = nullptr;
  auto fail = [&](int rc) {
    std::free(rows); std::free(cols); std::free(vals);
    return rc;
  };

  while (p < end) {
    // start of line: skip blank lines, handle comments anywhere
    while (p < end && (*p == '\n' || *p == '\r')) ++p;
    if (p >= end) break;
    const char* nl = (const char*)memchr(p, '\n', end - p);
    const char* lend = nl ? nl : end;
    if (*p == '%') {
      if (!header_done && (size_t)(lend - p) > 14 &&
          std::strncmp(p, "%%MatrixMarket", 14) == 0) {
        std::string line(p, lend);
        symmetric = line.find("symmetric") != std::string::npos;
        skew = line.find("skew-symmetric") != std::string::npos;
        pattern = line.find("pattern") != std::string::npos;
      }
      p = lend;
      continue;
    }
    char* q;
    if (!header_done) {
      nr = strtoll(p, &q, 10);
      if (q == p) return fail(2);
      p = q;
      nc = strtoll(p, &q, 10);
      if (q == p) return fail(2);
      p = q;
      declared = strtoll(p, &q, 10);
      if (q == p) return fail(2);
      if (nr <= 0 || nc <= 0 || declared < 0) return fail(2);
      cap = (symmetric || skew) ? 2 * (size_t)declared : (size_t)declared;
      if (cap == 0) cap = 1;               // malloc(0) may return NULL
      rows = (i32*)std::malloc(cap * sizeof(i32));
      cols = (i32*)std::malloc(cap * sizeof(i32));
      vals = (float*)std::malloc(cap * sizeof(float));
      if (!rows || !cols || !vals) return fail(3);
      header_done = true;
      p = lend;
      continue;
    }
    long long i = strtoll(p, &q, 10);
    if (q == p) return fail(2);
    p = q;
    long long j = strtoll(p, &q, 10);
    if (q == p) return fail(2);
    p = q;
    double v = 1.0;
    if (!pattern) {
      v = strtod(p, &q);
      if (q == p) return fail(2);
    }
    --i; --j;
    if (i < 0 || j < 0 || i >= nr || j >= nc || nnz >= cap) return fail(2);
    rows[nnz] = (i32)i; cols[nnz] = (i32)j; vals[nnz] = (float)v;
    ++nnz;
    if ((symmetric || skew) && i != j) {
      if (nnz >= cap) return fail(2);
      rows[nnz] = (i32)j; cols[nnz] = (i32)i;
      vals[nnz] = skew ? -(float)v : (float)v;
      ++nnz;
    }
    p = lend;                              // extra tokens (complex) ignored
  }
  if (!header_done) return fail(2);
  *nrows_out = nr; *ncols_out = nc; *nnz_out = (i64)nnz;
  *row_out = rows; *col_out = cols; *val_out = vals;
  return 0;
}

void sgcn_free(void* ptr) { std::free(ptr); }

}  // extern "C"

// ===================================================================== CLI
// sgcnpart -a graph.mtx -k 4 [-m g|h|r] [-o out.part] [-e imbalance] [-s seed]
// Reference CLI analogues: GCN-GP/main.cpp (gcngp), GCN-HP/main.cpp (gcnhgp),
// GPU/graph + GPU/hypergraph partvec generators.
#ifdef SGCNPART_MAIN
#include <chrono>
#include <cmath>
#include <fstream>
#include <sstream>

namespace {

struct Coo { i32 n = 0; std::vector<i32> row, col; std::vector<float> val; };

bool read_mtx(const std::string& path, Coo& out) {
  // thin wrapper over the shared buffer-scanning parser (sgcn_read_mtx)
  i64 nr = 0, nc = 0, nnz = 0;
  i32 *rows = nullptr, *cols = nullptr;
  float* vals = nullptr;
  int rc = sgcn_read_mtx(path.c_str(), &nr, &nc, &nnz, &rows, &cols, &vals);
  if (rc != 0) {
    const char* why = rc == 1 ? "cannot open"
                    : rc == 3 ? "out of memory reading"
                    : "malformed mtx";
    std::fprintf(stderr, "%s %s\n", why, path.c_str());
    return false;
  }
  out.n = (i32)std::max(nr, nc);
  out.row.assign(rows, rows + nnz);
  out.col.assign(cols, cols + nnz);
  out.val.assign(vals, vals + nnz);
  sgcn_free(rows); sgcn_free(cols); sgcn_free(vals);
  return true;
}

}  // namespace

int main(int argc, char** argv) {
  std::string path, out_path;
  int k = 2, seed = 1, replica_budget = 0;
  double imbalance = 0.03;
  char mode = 'h';
  for (int i = 1; i < argc; ++i) {
    std::string a = argv[i];
    auto next = [&]() -> std::string { return i + 1 < argc ? argv[++i] : ""; };
    if (a == "-a") path = next();
    else if (a == "-k") k = std::stoi(next());
    else if (a == "-m") mode = next()[0];
    else if (a == "-o") out_path = next();
    else if (a == "-e") imbalance = std::stod(next());
    else if (a == "-s") seed = std::stoi(next());
    else if (a == "-B") replica_budget = std::stoi(next());
    else { std::fprintf(stderr, "unknown flag %s\n", a.c_str()); return 2; }
  }
  if (path.empty() || k < 1 ||
      (mode != 'g' && mode != 'h' && mode != 'r') ||
      (replica_budget > 0 && mode != 'h')) {
    std::fprintf(stderr,
        "usage: sgcnpart -a graph.mtx -k K [-m g|h|r] [-o out] [-e imb] "
        "[-s seed] [-B replica_budget (mode h only: cache-aware km1)]\n");
    return 2;
  }
  Coo coo;
  if (!read_mtx(path, coo)) { std::fprintf(stderr, "cannot read %s\n", path.c_str()); return 1; }
  i32 n = coo.n;
  std::vector<i32> part(n, 0);
  i64 metric = 0, metric_cache = 0;
  auto t0 = std::chrono::steady_clock::now();
  if (mode == 'r') {
    Rng rng((uint64_t)seed);
    for (i32 v = 0; v < n; ++v) part[v] = (i32)rng.below(k);
  } else if (mode == 'g') {
    // symmetrize into CSR (graph model), dedup'd: the reader already expands
    // symmetric storage, and general files may list both directions
    std::vector<i64> keys;
    keys.reserve(2 * coo.row.size());
    for (size_t e = 0; e < coo.row.size(); ++e) {
      i64 i = coo.row[e], j = coo.col[e];
      if (i == j) continue;
      keys.push_back(i * (i64)n + j);
      keys.push_back(j * (i64)n + i);
    }
    std::sort(keys.begin(), keys.end());
    keys.erase(std::unique(keys.begin(), keys.end()), keys.end());
    std::vector<i64> xadj(n + 1, 0);
    std::vector<i32> adj(keys.size());
    std::vector<float> wgt(keys.size(), 1.0f);
    for (i64 key : keys) xadj[key / n + 1]++;
    for (i32 v = 0; v < n; ++v) xadj[v + 1] += xadj[v];
    for (size_t e = 0; e < keys.size(); ++e) adj[e] = (i32)(keys[e] % n);
    sgcn_partition_graph(n, xadj.data(), adj.data(), wgt.data(), nullptr, k,
                         imbalance, seed, part.data(), &metric);
  } else {
    // column-net hypergraph: cells = rows, nets = cols, weight = row nnz
    std::vector<i64> cellptr(n + 1, 0);
    for (size_t e = 0; e < coo.row.size(); ++e) cellptr[coo.row[e] + 1]++;
    std::vector<i64> cwgt(n);
    for (i32 v = 0; v < n; ++v) { cwgt[v] = std::max<i64>(1, cellptr[v + 1]); }
    for (i32 v = 0; v < n; ++v) cellptr[v + 1] += cellptr[v];
    std::vector<i32> cellnets(coo.row.size());
    std::vector<i64> pos(cellptr.begin(), cellptr.end() - 1);
    for (size_t e = 0; e < coo.row.size(); ++e)
      cellnets[pos[coo.row[e]]++] = coo.col[e];
    if (replica_budget > 0)
      sgcn_partition_hypergraph_cache(
          n, n, cellptr.data(), cellnets.data(), cwgt.data(), k, imbalance,
          seed, replica_budget, part.data(), &metric, &metric_cache);
    else
      sgcn_partition_hypergraph(n, n, cellptr.data(), cellnets.data(),
                                cwgt.data(), k, imbalance, seed, part.data(),
                                &metric);
  }
  auto t1 = std::chrono::steady_clock::now();
  double secs = std::chrono::duration<double>(t1 - t0).count();
  // part sizes for the balance report
  std::vector<i64> sizes(k, 0);
  for (i32 v = 0; v < n; ++v) sizes[part[v]]++;
  i64 maxs = *std::max_element(sizes.begin(), sizes.end());
  if (replica_budget > 0)
    std::printf("n=%d k=%d mode=%c metric=%lld metric_cache=%lld B=%d "
                "max_part=%lld time_s=%.3f\n",
                n, k, mode, (long long)metric, (long long)metric_cache,
                replica_budget, (long long)maxs, secs);
  else
    std::printf("n=%d k=%d mode=%c metric=%lld max_part=%lld time_s=%.3f\n",
                n, k, mode, (long long)metric, (long long)maxs, secs);
  if (!out_path.empty()) {
    std::ofstream o(out_path);
    for (i32 v = 0; v < n; ++v) o << part[v] << "\n";
  }
  return 0;
}
#endif  // SGCNPART_MAIN

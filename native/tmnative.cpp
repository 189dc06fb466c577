// tmnative: first-party native host kernels.
//
// Reference parity: the reference's performance-critical host code lives in
// third-party C++ (cv2, mahotas — SURVEY.md §3 "external binary deps"); the
// TPU rebuild keeps device math in XLA and implements its own native host
// kernels for the two pathways that stay on the CPU:
//
//   1. union-find connected-component labeling (scipy scan order) — the
//      host-side golden/fallback for the device labeler and the fast path
//      for host-only workflows (ingest QC, tests);
//   2. Moore-neighbor boundary tracing — polygon extraction for the object
//      store (reference: PostGIS polygons via shapely/cv2).
//
// Built as a plain shared library, loaded via ctypes (no pybind11 in the
// image). C ABI only.

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <array>
#include <cstring>
#include <limits>
#include <utility>
#include <vector>

namespace {

struct UnionFind {
  std::vector<int32_t> parent;
  explicit UnionFind(size_t n) : parent(n) {
    for (size_t i = 0; i < n; ++i) parent[i] = static_cast<int32_t>(i);
  }
  int32_t find(int32_t x) {
    while (parent[x] != x) {
      parent[x] = parent[parent[x]];  // path halving
      x = parent[x];
    }
    return x;
  }
  void unite(int32_t a, int32_t b) {
    a = find(a);
    b = find(b);
    if (a == b) return;
    // keep the smaller root: scan-order labeling falls out of this
    if (a < b) parent[b] = a; else parent[a] = b;
  }
};

}  // namespace

extern "C" {

// Label the foreground (mask != 0) with 4- or 8-connectivity.
// labels_out receives 0 for background, 1..N in scipy scan order
// (components numbered by first pixel in row-major order).
// Returns N, or -1 on invalid arguments.
int32_t tm_cc_label(const uint8_t* mask, int32_t h, int32_t w,
                    int32_t connectivity, int32_t* labels_out) {
  if (!mask || !labels_out || h <= 0 || w <= 0) return -1;
  if (connectivity != 4 && connectivity != 8) return -1;
  const size_t n = static_cast<size_t>(h) * static_cast<size_t>(w);
  UnionFind uf(n);

  // one pass of neighbor unions (only look up/left — prior pixels)
  for (int32_t y = 0; y < h; ++y) {
    for (int32_t x = 0; x < w; ++x) {
      const size_t i = static_cast<size_t>(y) * w + x;
      if (!mask[i]) continue;
      if (x > 0 && mask[i - 1]) uf.unite(static_cast<int32_t>(i), static_cast<int32_t>(i - 1));
      if (y > 0) {
        const size_t up = i - w;
        if (mask[up]) uf.unite(static_cast<int32_t>(i), static_cast<int32_t>(up));
        if (connectivity == 8) {
          if (x > 0 && mask[up - 1]) uf.unite(static_cast<int32_t>(i), static_cast<int32_t>(up - 1));
          if (x + 1 < w && mask[up + 1]) uf.unite(static_cast<int32_t>(i), static_cast<int32_t>(up + 1));
        }
      }
    }
  }

  // second pass: roots are component minima (smaller-root union), so
  // numbering roots in scan order reproduces scipy.ndimage.label exactly
  std::vector<int32_t> remap(n, 0);
  int32_t next = 0;
  for (size_t i = 0; i < n; ++i) {
    if (!mask[i]) { labels_out[i] = 0; continue; }
    const int32_t r = uf.find(static_cast<int32_t>(i));
    if (remap[r] == 0) remap[r] = ++next;
    labels_out[i] = remap[r];
  }
  return next;
}

// Moore-neighbor boundary trace of one labeled object (8-connected
// boundary, clockwise, starting at the first pixel in scan order).
// out_yx receives up to max_pts (y, x) pairs; returns the number of
// points, 0 if the label is absent, or -1 on invalid arguments.
int32_t tm_trace_boundary(const int32_t* labels, int32_t h, int32_t w,
                          int32_t label, int32_t* out_yx, int32_t max_pts) {
  if (!labels || !out_yx || h <= 0 || w <= 0 || max_pts <= 0) return -1;
  auto at = [&](int32_t y, int32_t x) -> bool {
    return y >= 0 && y < h && x >= 0 && x < w &&
           labels[static_cast<size_t>(y) * w + x] == label;
  };
  // first pixel in scan order
  int32_t sy = -1, sx = -1;
  for (int32_t y = 0; y < h && sy < 0; ++y)
    for (int32_t x = 0; x < w; ++x)
      if (at(y, x)) { sy = y; sx = x; break; }
  if (sy < 0) return 0;

  // clockwise Moore neighborhood order: W, NW, N, NE, E, SE, S, SW
  static const int32_t dy[8] = {0, -1, -1, -1, 0, 1, 1, 1};
  static const int32_t dx[8] = {-1, -1, 0, 1, 1, 1, 0, -1};

  // Moore tracing with explicit backtrack + Jacob's stopping criterion:
  // stop when the start pixel is re-entered from its original backtrack.
  int32_t cy = sy, cx = sx;
  int32_t back = 0;  // direction from current to backtrack; start = west
  const int32_t back0 = back;
  int32_t count = 0;
  const int64_t limit = static_cast<int64_t>(h) * w * 4 + 8;
  for (int64_t iter = 0; iter < limit; ++iter) {
    if (iter == 0 || !(cy == sy && cx == sx)) {
      if (count < max_pts) {
        out_yx[2 * count] = cy;
        out_yx[2 * count + 1] = cx;
      }
      ++count;
    }
    // scan clockwise from just past the backtrack neighbor
    int32_t k = 1;
    int32_t d = -1;
    for (; k <= 8; ++k) {
      d = (back + k) % 8;
      if (at(cy + dy[d], cx + dx[d])) break;
    }
    if (k > 8) break;  // isolated pixel
    // move; the new backtrack is the neighbor scanned just before d,
    // expressed as a direction from the NEW pixel
    const int32_t prev = (back + k - 1) % 8;
    const int32_t py = cy + dy[prev], px = cx + dx[prev];
    cy += dy[d];
    cx += dx[d];
    // direction from new current back to that previous (background) pixel
    back = 0;
    for (int32_t j = 0; j < 8; ++j) {
      if (cy + dy[j] == py && cx + dx[j] == px) { back = j; break; }
    }
    if (cy == sy && cx == sx && back == back0) break;
  }
  // return the TRUE count even when it exceeds max_pts, so callers can
  // detect truncation and retry with a larger buffer
  return count;
}

// Per-object bounding boxes: out receives (min_y, min_x, max_y, max_x) per
// label 1..max_label (rows of 4); labels absent get (-1,-1,-1,-1).
void tm_bounding_boxes(const int32_t* labels, int32_t h, int32_t w,
                       int32_t max_label, int32_t* out) {
  for (int32_t l = 0; l < max_label; ++l) {
    out[4 * l] = -1; out[4 * l + 1] = -1; out[4 * l + 2] = -1; out[4 * l + 3] = -1;
  }
  for (int32_t y = 0; y < h; ++y) {
    for (int32_t x = 0; x < w; ++x) {
      const int32_t v = labels[static_cast<size_t>(y) * w + x];
      if (v < 1 || v > max_label) continue;
      int32_t* b = out + 4 * (v - 1);
      if (b[0] < 0) { b[0] = y; b[1] = x; b[2] = y; b[3] = x; }
      else {
        if (y < b[0]) b[0] = y;
        if (x < b[1]) b[1] = x;
        if (y > b[2]) b[2] = y;
        if (x > b[3]) b[3] = x;
      }
    }
  }
}

// Per-object rasterized convex hull pixel counts (skimage
// convex_hull_image semantics over pixel centers): for each label
// 1..max_label, out[l-1] receives the number of pixels whose center lies
// inside or on the convex hull of the object's pixel centers.  Labels
// absent get 0.  Solidity = area / hull_count falls out on the caller
// side.  Returns 0, or -1 on invalid arguments.
int32_t tm_hull_pixel_counts(const int32_t* labels, int32_t h, int32_t w,
                             int32_t max_label, int32_t* out) {
  if (!labels || !out || h <= 0 || w <= 0 || max_label <= 0) return -1;
  std::memset(out, 0, sizeof(int32_t) * static_cast<size_t>(max_label));

  // gather per-label bounding boxes + pixel lists in one scan
  std::vector<int32_t> bbox(static_cast<size_t>(max_label) * 4);
  for (int32_t l = 0; l < max_label; ++l) {
    bbox[4 * l] = -1; bbox[4 * l + 1] = -1; bbox[4 * l + 2] = -1; bbox[4 * l + 3] = -1;
  }
  std::vector<std::vector<std::pair<int32_t, int32_t>>> pts(max_label);
  for (int32_t y = 0; y < h; ++y) {
    for (int32_t x = 0; x < w; ++x) {
      const int32_t v = labels[static_cast<size_t>(y) * w + x];
      if (v < 1 || v > max_label) continue;
      int32_t* b = &bbox[4 * (v - 1)];
      if (b[0] < 0) { b[0] = y; b[1] = x; b[2] = y; b[3] = x; }
      else {
        if (y < b[0]) b[0] = y;
        if (x < b[1]) b[1] = x;
        if (y > b[2]) b[2] = y;
        if (x > b[3]) b[3] = x;
      }
      pts[v - 1].emplace_back(x, y);
    }
  }

  auto cross = [](int64_t ox, int64_t oy, int64_t ax, int64_t ay,
                  int64_t bx, int64_t by) -> int64_t {
    return (ax - ox) * (by - oy) - (ay - oy) * (bx - ox);
  };

  for (int32_t l = 0; l < max_label; ++l) {
    auto& p = pts[l];
    const size_t n = p.size();
    if (n == 0) continue;
    if (n <= 2) { out[l] = static_cast<int32_t>(n); continue; }
    // Andrew's monotone chain (points are already sorted by (y, x) from the
    // scan; re-sort by (x, y) as the algorithm expects)
    std::sort(p.begin(), p.end());
    std::vector<std::pair<int32_t, int32_t>> hull(2 * n);
    size_t k = 0;
    for (size_t i = 0; i < n; ++i) {            // lower hull
      while (k >= 2 && cross(hull[k - 2].first, hull[k - 2].second,
                             hull[k - 1].first, hull[k - 1].second,
                             p[i].first, p[i].second) <= 0) --k;
      hull[k++] = p[i];
    }
    for (size_t i = n - 1, t = k + 1; i-- > 0;) {  // upper hull
      while (k >= t && cross(hull[k - 2].first, hull[k - 2].second,
                             hull[k - 1].first, hull[k - 1].second,
                             p[i].first, p[i].second) <= 0) --k;
      hull[k++] = p[i];
    }
    hull.resize(k - 1);  // last point == first point
    const size_t m = hull.size();
    if (m <= 2) {  // degenerate (collinear object): hull pixels = object pixels
      out[l] = static_cast<int32_t>(n);
      continue;
    }
    // hull is counter-clockwise in (x, y) with cross<=0 popped: a pixel
    // center is inside-or-on iff it is left of (cross >= 0) every edge
    const int32_t* b = &bbox[4 * l];
    int32_t count = 0;
    for (int32_t y = b[0]; y <= b[2]; ++y) {
      for (int32_t x = b[1]; x <= b[3]; ++x) {
        bool inside = true;
        for (size_t i = 0; i < m && inside; ++i) {
          const auto& a0 = hull[i];
          const auto& a1 = hull[(i + 1) % m];
          if (cross(a0.first, a0.second, a1.first, a1.second, x, y) < 0)
            inside = false;
        }
        if (inside) ++count;
      }
    }
    out[l] = count;
  }
  return 0;
}

// Douglas-Peucker simplification of a closed (y, x) contour ring.
// pts: n rows of (y, x); keep: n flags (out), 1 = vertex survives.
// tol: perpendicular-distance tolerance in pixels.  The ring is split at
// vertex 0 and its farthest vertex (both always kept) so the closing
// edge is simplified like any other.  Returns the number of kept
// vertices, or -1 on invalid arguments.
int32_t tm_simplify_polygon(const int32_t* pts, int32_t n, double tol,
                            uint8_t* keep) {
  if (!pts || !keep || n < 0) return -1;
  std::memset(keep, 0, static_cast<size_t>(n));
  if (n <= 2) {
    for (int32_t i = 0; i < n; ++i) keep[i] = 1;
    return n;
  }
  const double tol2 = tol * tol;
  auto px = [&](int32_t i) { return static_cast<double>(pts[2 * i + 1]); };
  auto py = [&](int32_t i) { return static_cast<double>(pts[2 * i]); };

  // squared perpendicular distance of vertex i to chord (a, b)
  auto dist2 = [&](int32_t i, int32_t a, int32_t b) {
    const double ax = px(a), ay = py(a), bx = px(b), by = py(b);
    const double dx = bx - ax, dy = by - ay;
    const double len2 = dx * dx + dy * dy;
    if (len2 == 0.0) {
      const double ex = px(i) - ax, ey = py(i) - ay;
      return ex * ex + ey * ey;
    }
    const double cross = dx * (py(i) - ay) - dy * (px(i) - ax);
    return cross * cross / len2;
  };

  // split the ring at the vertex farthest from vertex 0
  int32_t far_i = 1;
  double far_d = -1.0;
  for (int32_t i = 1; i < n; ++i) {
    const double ex = px(i) - px(0), ey = py(i) - py(0);
    const double d = ex * ex + ey * ey;
    if (d > far_d) { far_d = d; far_i = i; }
  }
  keep[0] = 1;
  keep[far_i] = 1;

  // iterative DP over index ranges [a, b] (wrapping handled by the two
  // half-open arcs 0..far_i and far_i..n-1..(0))
  std::vector<std::pair<int32_t, int32_t>> stack;
  stack.emplace_back(0, far_i);
  stack.emplace_back(far_i, n);  // b == n means "chord ends at vertex 0"
  while (!stack.empty()) {
    const auto [a, b] = stack.back();
    stack.pop_back();
    const int32_t chord_b = (b == n) ? 0 : b;
    int32_t worst = -1;
    double worst_d = tol2;
    for (int32_t i = a + 1; i < b; ++i) {
      const double d = dist2(i, a, chord_b);
      if (d > worst_d) { worst_d = d; worst = i; }
    }
    if (worst >= 0) {
      keep[worst] = 1;
      stack.emplace_back(a, worst);
      stack.emplace_back(worst, b);
    }
  }
  int32_t kept = 0;
  for (int32_t i = 0; i < n; ++i) kept += keep[i];
  return kept;
}

}  // extern "C"


// ---------------------------------------------------------------------------
// Minimal TIFF reader: the native data-loader for imextract.
//
// Reference parity: the reference's image ingest leans on Bio-Formats (Java)
// and cv2 (C++) for plane decoding (SURVEY.md §3 readers row); this is the
// first-party replacement covering the formats microscopes actually emit as
// plain TIFF: classic little/big-endian TIFF, strip-organized, grayscale
// 8/16-bit, uncompressed / LZW (with horizontal predictor) / PackBits,
// multi-page.  Anything else returns an error and the Python caller falls
// back to cv2.
// ---------------------------------------------------------------------------

#include <cstdio>

namespace tifflite {

struct Buf {
  std::vector<uint8_t> d;
  bool le = true;
  uint16_t rd16(size_t o) const {
    if (o + 2 > d.size()) return 0;
    return le ? (uint16_t)(d[o] | (d[o + 1] << 8))
              : (uint16_t)((d[o] << 8) | d[o + 1]);
  }
  uint32_t rd32(size_t o) const {
    if (o + 4 > d.size()) return 0;
    return le ? ((uint32_t)d[o] | ((uint32_t)d[o + 1] << 8) |
                 ((uint32_t)d[o + 2] << 16) | ((uint32_t)d[o + 3] << 24))
              : (((uint32_t)d[o] << 24) | ((uint32_t)d[o + 1] << 16) |
                 ((uint32_t)d[o + 2] << 8) | (uint32_t)d[o + 3]);
  }
};

static bool load_file(const char* path, Buf& b) {
  FILE* f = std::fopen(path, "rb");
  if (!f) return false;
  // reject non-TIFF from the 4-byte header BEFORE slurping the file, so a
  // PNG handed to the reader costs 4 bytes of IO, not a full read
  uint8_t hdr[4];
  if (std::fread(hdr, 1, 4, f) != 4) { std::fclose(f); return false; }
  if (hdr[0] == 'I' && hdr[1] == 'I') b.le = true;
  else if (hdr[0] == 'M' && hdr[1] == 'M') b.le = false;
  else { std::fclose(f); return false; }
  uint16_t magic = b.le ? (uint16_t)(hdr[2] | (hdr[3] << 8))
                        : (uint16_t)((hdr[2] << 8) | hdr[3]);
  if (magic != 42) { std::fclose(f); return false; }  // classic TIFF only
  std::fseek(f, 0, SEEK_END);
  long sz = std::ftell(f);
  if (sz <= 8) { std::fclose(f); return false; }
  std::fseek(f, 0, SEEK_SET);
  b.d.resize((size_t)sz);
  size_t got = std::fread(b.d.data(), 1, (size_t)sz, f);
  std::fclose(f);
  return got == (size_t)sz;
}

// cap on IFD-chain walks: bounds page counts AND terminates on cyclic
// next-IFD pointers in corrupt/malicious files
constexpr int32_t kMaxPages = 65535;

struct Entry { uint16_t type; uint32_t count; size_t value_off; };

// value_off points at the 4-byte value field itself; values larger than
// 4 bytes live at the offset stored there.
static size_t entry_data(const Buf& b, const Entry& e, size_t elem_size) {
  size_t total = (size_t)e.count * elem_size;
  return total <= 4 ? e.value_off : (size_t)b.rd32(e.value_off);
}

static uint32_t entry_int(const Buf& b, const Entry& e, uint32_t idx) {
  size_t elem = e.type == 3 ? 2 : 4;  // SHORT or LONG
  size_t base = entry_data(b, e, elem);
  return elem == 2 ? b.rd16(base + 2 * idx) : b.rd32(base + 4 * idx);
}

struct IFD {
  uint32_t width = 0, height = 0, bits = 0, compression = 1;
  uint32_t samples = 1, rows_per_strip = 0xFFFFFFFFu, predictor = 1;
  std::vector<size_t> strip_offsets, strip_counts;
};

static bool parse_ifd(const Buf& b, size_t off, IFD& out, size_t* next) {
  if (off == 0 || off + 2 > b.d.size()) return false;
  uint16_t n = b.rd16(off);
  size_t p = off + 2;
  if (p + 12 * (size_t)n + 4 > b.d.size()) return false;
  Entry so{0, 0, 0}, sc{0, 0, 0};
  for (uint16_t i = 0; i < n; ++i, p += 12) {
    uint16_t tag = b.rd16(p);
    Entry e{b.rd16(p + 2), b.rd32(p + 4), p + 8};
    switch (tag) {
      case 256: out.width = entry_int(b, e, 0); break;
      case 257: out.height = entry_int(b, e, 0); break;
      case 258: out.bits = entry_int(b, e, 0); break;
      case 259: out.compression = entry_int(b, e, 0); break;
      case 273: so = e; break;
      case 277: out.samples = entry_int(b, e, 0); break;
      case 278: out.rows_per_strip = entry_int(b, e, 0); break;
      case 279: sc = e; break;
      case 317: out.predictor = entry_int(b, e, 0); break;
      default: break;
    }
  }
  *next = b.rd32(p);
  if (so.count == 0 || sc.count == 0 || so.count != sc.count) return false;
  for (uint32_t i = 0; i < so.count; ++i) {
    out.strip_offsets.push_back(entry_int(b, so, i));
    out.strip_counts.push_back(entry_int(b, sc, i));
  }
  return out.width > 0 && out.height > 0;
}

static bool lzw_decode(const uint8_t* src, size_t n, std::vector<uint8_t>& out,
                       size_t expect) {
  // TIFF LZW: MSB-first codes, 256=Clear, 257=EOI, early code-width
  // change.  Output-reference table: every code's expansion is a
  // substring of the ALREADY-DECODED output (entry next_free is the
  // previous emission plus the first byte of the current one — two
  // consecutive appends, so its bytes are contiguous in `out`), so each
  // entry stores (output offset, length) and emitting a string is ONE
  // memcpy from earlier output instead of a per-byte chain walk +
  // reverse (the chain-table form this replaces ran ~160 MB/s; the copy
  // form removes the O(length) pointer chase per code).
  uint32_t tpos[4096];
  uint32_t tlen[4096];
  int next_free = 258;
  // ONE up-front allocation sized expect + the largest possible single
  // emission (4095) + 8 bytes of chunked-copy overrun margin: the hot
  // loop then writes through a raw pointer with no growth checks, and
  // the 8-byte block copies below may read/write up to 7 bytes past a
  // string's end, always inside this buffer
  out.assign(expect + 4104, 0);
  uint8_t* o = out.data();
  size_t olen = 0;
  size_t pos = 0;
  uint64_t acc = 0;
  int nbits = 0;
  int width = 9;
  int prev = -1;
  uint32_t prev_pos = 0, prev_len = 0;
  while (olen < expect) {
    if (nbits < width) {  // bulk refill: ~once per several codes
      while (nbits <= 56 && pos < n) {
        acc = (acc << 8) | src[pos++];
        nbits += 8;
      }
      if (nbits < width) break;  // truncated stream
    }
    nbits -= width;
    int code = (int)((acc >> nbits) & ((1u << width) - 1));
    if (code == 257) break;  // EOI
    if (code == 256) {       // Clear
      next_free = 258;
      width = 9;
      prev = -1;
      continue;
    }
    const uint32_t at = (uint32_t)olen;
    uint32_t len;
    if (prev < 0) {
      // first code after Clear must be a literal
      if (code > 255) { out.resize(olen); return false; }
      o[olen++] = (uint8_t)code;
      prev = code;
      prev_pos = at;
      prev_len = 1;
      continue;
    }
    if (code < 256) {
      o[olen++] = (uint8_t)code;
      len = 1;
    } else if (code < next_free) {
      len = tlen[code];
      const uint8_t* s = o + tpos[code];
      uint8_t* d = o + at;
      if (at - tpos[code] >= 8) {
        // 8-byte chunks; the ≤7-byte tail overrun lands in dest bytes
        // the next emission (or the final resize) overwrites/discards
        for (uint32_t i = 0; i < len; i += 8) std::memcpy(d + i, s + i, 8);
      } else {  // source too close to dest for chunking (e.g. "ababab")
        for (uint32_t i = 0; i < len; ++i) d[i] = s[i];
      }
      olen += len;
    } else if (code == next_free) {
      // KwKwK: previous string + its own first byte
      len = prev_len + 1;
      const uint8_t* s = o + prev_pos;
      uint8_t* d = o + at;
      if (at - prev_pos >= 8) {
        for (uint32_t i = 0; i < prev_len; i += 8)
          std::memcpy(d + i, s + i, 8);
      } else {
        for (uint32_t i = 0; i < prev_len; ++i) d[i] = s[i];
      }
      d[prev_len] = s[0];
      olen += len;
    } else {
      out.resize(olen);
      return false;  // corrupt stream
    }
    if (next_free < 4096) {
      // previous emission [prev_pos, prev_pos+prev_len) is immediately
      // followed by this one, so the new entry's bytes are contiguous
      tpos[next_free] = prev_pos;
      tlen[next_free] = prev_len + 1;
      ++next_free;
    }
    // early change: width grows when the NEXT code would not fit
    if (next_free + 1 >= (1 << width) && width < 12) ++width;
    prev = code;
    prev_pos = at;
    prev_len = len;
  }
  out.resize(olen);
  return olen >= expect;
}

static bool packbits_decode(const uint8_t* src, size_t n,
                            std::vector<uint8_t>& out, size_t expect) {
  out.clear();
  out.reserve(expect);
  size_t i = 0;
  while (i < n && out.size() < expect) {
    int8_t c = (int8_t)src[i++];
    if (c >= 0) {
      size_t cnt = (size_t)c + 1;
      if (i + cnt > n) return false;
      out.insert(out.end(), src + i, src + i + cnt);
      i += cnt;
    } else if (c != -128) {
      if (i >= n) return false;
      out.insert(out.end(), (size_t)(1 - c), src[i++]);
    }
  }
  return out.size() >= expect;
}

// Walk to page `page`; -1 errors, else fills ifd.
static int walk(const Buf& b, int32_t page, IFD& ifd) {
  if (page >= kMaxPages) return -1;
  size_t off = b.rd32(4);
  for (int32_t i = 0; i < kMaxPages; ++i) {
    IFD cur;
    size_t next = 0;
    if (!parse_ifd(b, off, cur, &next)) return -1;
    if (i == page) { ifd = cur; return 0; }
    if (next == 0) return -1;
    off = next;
  }
  return -1;
}

}  // namespace tifflite

extern "C" {

// Raw TIFF-variant LZW strip decode (MSB-first codes, early width change)
// into a caller-sized buffer.  Exported for the Python container readers
// (Zeiss LSM strips are usually LZW) — the pure-Python bit-unpacking twin
// is ~100x slower on megabyte strips.  Returns 1 on success, 0 on corrupt
// input or short output.
int32_t tm_lzw_decode(const uint8_t* src, int64_t n, uint8_t* out,
                      int64_t expect) {
  if (!src || !out || n < 0 || expect < 0) return 0;
  std::vector<uint8_t> buf;
  if (!tifflite::lzw_decode(src, (size_t)n, buf, (size_t)expect)) return 0;
  std::memcpy(out, buf.data(), (size_t)expect);
  return 1;
}

// PackBits strip decode, same contract as tm_lzw_decode.
int32_t tm_packbits_decode(const uint8_t* src, int64_t n, uint8_t* out,
                           int64_t expect) {
  if (!src || !out || n < 0 || expect < 0) return 0;
  std::vector<uint8_t> buf;
  if (!tifflite::packbits_decode(src, (size_t)n, buf, (size_t)expect)) return 0;
  std::memcpy(out, buf.data(), (size_t)expect);
  return 1;
}

// out4: [n_pages, height, width, bits] of page 0.  Returns 0, or -1 when
// the file is not a TIFF this reader handles.
int32_t tm_tiff_info(const char* path, int32_t* out4) {
  if (!path || !out4) return -1;
  tifflite::Buf b;
  if (!tifflite::load_file(path, b)) return -1;
  tifflite::IFD first;
  size_t off = b.rd32(4), next = 0;
  if (!tifflite::parse_ifd(b, off, first, &next)) return -1;
  int32_t pages = 1;
  while (next != 0 && pages < tifflite::kMaxPages) {
    tifflite::IFD cur;
    size_t nn = 0;
    if (!tifflite::parse_ifd(b, next, cur, &nn)) break;
    ++pages;
    next = nn;
  }
  out4[0] = pages;
  out4[1] = (int32_t)first.height;
  out4[2] = (int32_t)first.width;
  out4[3] = (int32_t)first.bits;
  return 0;
}

// Decode grayscale page `page` into out (row-major uint16, h*w elements,
// 8-bit samples are widened).  Returns 0 on success; -1 on any
// parse/shape/unsupported-feature condition (caller falls back to cv2).
static int32_t tiff_decode_gray(const tifflite::Buf& b,
                                const tifflite::IFD& ifd, uint16_t* out,
                                int32_t h, int32_t w) {
  if (ifd.samples != 1) return -1;                    // grayscale only
  if (ifd.bits != 8 && ifd.bits != 16) return -1;
  if (ifd.predictor != 1 && ifd.predictor != 2) return -1;

  const size_t bytes_per_row = (size_t)w * (ifd.bits / 8);
  std::vector<uint8_t> plane;
  plane.reserve(bytes_per_row * (size_t)h);
  uint32_t rps = ifd.rows_per_strip ? ifd.rows_per_strip : (uint32_t)h;
  std::vector<uint8_t> strip;
  for (size_t s = 0; s < ifd.strip_offsets.size(); ++s) {
    uint32_t rows = rps;
    uint32_t row0 = (uint32_t)s * rps;
    if (row0 >= (uint32_t)h) break;
    if (row0 + rows > (uint32_t)h) rows = (uint32_t)h - row0;
    size_t expect = bytes_per_row * rows;
    size_t off = ifd.strip_offsets[s], cnt = ifd.strip_counts[s];
    if (off + cnt > b.d.size()) return -1;
    const uint8_t* src = b.d.data() + off;
    if (ifd.compression == 1) {
      if (cnt < expect) return -1;
      plane.insert(plane.end(), src, src + expect);
    } else if (ifd.compression == 5) {
      if (!tifflite::lzw_decode(src, cnt, strip, expect)) return -1;
      plane.insert(plane.end(), strip.begin(), strip.begin() + expect);
    } else if (ifd.compression == 32773) {
      if (!tifflite::packbits_decode(src, cnt, strip, expect)) return -1;
      plane.insert(plane.end(), strip.begin(), strip.begin() + expect);
    } else {
      return -1;  // unsupported codec
    }
  }
  if (plane.size() < bytes_per_row * (size_t)h) return -1;

  // samples -> uint16 with file byte order, then the horizontal predictor
  for (int32_t y = 0; y < h; ++y) {
    const uint8_t* row = plane.data() + (size_t)y * bytes_per_row;
    uint16_t* dst = out + (size_t)y * (size_t)w;
    if (ifd.bits == 8) {
      for (int32_t x = 0; x < w; ++x) dst[x] = row[x];
    } else {
      for (int32_t x = 0; x < w; ++x) {
        dst[x] = b.le ? (uint16_t)(row[2 * x] | (row[2 * x + 1] << 8))
                      : (uint16_t)((row[2 * x] << 8) | row[2 * x + 1]);
      }
    }
    if (ifd.predictor == 2) {
      // horizontal differencing accumulates in the SAMPLE width: 8-bit
      // samples wrap at 256, 16-bit at 65536
      if (ifd.bits == 8) {
        for (int32_t x = 1; x < w; ++x)
          dst[x] = (uint16_t)((dst[x] + dst[x - 1]) & 0xFF);
      } else {
        for (int32_t x = 1; x < w; ++x)
          dst[x] = (uint16_t)(dst[x] + dst[x - 1]);
      }
    }
  }
  return 0;
}

int32_t tm_tiff_read(const char* path, int32_t page, uint16_t* out,
                     int32_t h, int32_t w) {
  if (!path || !out || h <= 0 || w <= 0 || page < 0) return -1;
  tifflite::Buf b;
  if (!tifflite::load_file(path, b)) return -1;
  tifflite::IFD ifd;
  if (tifflite::walk(b, page, ifd) != 0) return -1;
  if ((int32_t)ifd.height != h || (int32_t)ifd.width != w) return -1;
  return tiff_decode_gray(b, ifd, out, h, w);
}

// Combined parse + decode in ONE file load: fills hw_out[0..2] with the
// page's height/width/bits and decodes into `out` when h*w fits
// `capacity` pixels.  Returns 0 on success, -2 when the capacity is too small
// (hw_out is still filled so the caller retries sized exactly), -1 on
// anything the paged reader does not handle.  Exists because the
// info-then-read protocol loaded and walked the file TWICE per page
// (~0.1 ms of the ~1 ms ingest cost per 256-px file).
int32_t tm_tiff_read2(const char* path, int32_t page, uint16_t* out,
                      int64_t capacity, int32_t* hw_out) {
  if (!path || !out || !hw_out || page < 0 || capacity < 0) return -1;
  tifflite::Buf b;
  if (!tifflite::load_file(path, b)) return -1;
  tifflite::IFD ifd;
  if (tifflite::walk(b, page, ifd) != 0) return -1;
  hw_out[0] = (int32_t)ifd.height;
  hw_out[1] = (int32_t)ifd.width;
  hw_out[2] = (int32_t)ifd.bits;
  if (ifd.height <= 0 || ifd.width <= 0) return -1;
  if ((int64_t)ifd.height * (int64_t)ifd.width > capacity) return -2;
  return tiff_decode_gray(b, ifd, out, (int32_t)ifd.height,
                          (int32_t)ifd.width);
}

}  // extern "C"

// ---------------------------------------------------------------------------
// CPU-fallback segmentation kernels (round-3).
//
// When jax.default_backend() == "cpu" the XLA twins of the iterative
// segmentation ops (lax.while_loop fixpoints) are pathological — the
// round-2 bench lost to single-thread scipy 2.5:1 on that path.  These
// kernels are routed in via jax.pure_callback (ops/label.py,
// ops/segment_primary.py, ops/segment_secondary.py, method="native") and
// replicate the device semantics EXACTLY, including tie-breaking, so the
// bit-identical label gate holds across backends.

namespace wsnative {

// Neighbor geometry policies: the ONLY thing that differs between the
// 2-D and 3-D floods.
struct Geo2 {
  int32_t h, w, connectivity;

  template <typename Fn>
  void for_neighbors(int32_t i, Fn fn) const {
    const int32_t y = i / w, x = i % w;
    if (x > 0) fn(i - 1);
    if (x + 1 < w) fn(i + 1);
    if (y > 0) fn(i - w);
    if (y + 1 < h) fn(i + w);
    if (connectivity == 8) {
      if (y > 0 && x > 0) fn(i - w - 1);
      if (y > 0 && x + 1 < w) fn(i - w + 1);
      if (y + 1 < h && x > 0) fn(i + w - 1);
      if (y + 1 < h && x + 1 < w) fn(i + w + 1);
    }
  }
};

// full 26-neighborhood (ops/volume.py _adopt_step_3d always uses it)
struct Geo3 {
  int32_t nz, h, w;

  template <typename Fn>
  void for_neighbors(int32_t i, Fn fn) const {
    const int32_t plane = h * w;
    const int32_t z = i / plane, rem = i % plane, y = rem / w, x = rem % w;
    for (int32_t dz = -1; dz <= 1; ++dz) {
      const int32_t zz = z + dz;
      if (zz < 0 || zz >= nz) continue;
      for (int32_t dy = -1; dy <= 1; ++dy) {
        const int32_t yy = y + dy;
        if (yy < 0 || yy >= h) continue;
        for (int32_t dx = -1; dx <= 1; ++dx) {
          if (!dz && !dy && !dx) continue;
          const int32_t xx = x + dx;
          if (xx < 0 || xx >= w) continue;
          fn(zz * plane + yy * w + xx);
        }
      }
    }
  }
};

// Shared level-loop body of tm_watershed_levels / tm_watershed_levels3d.
//
// Semantics are identical to ops/segment_secondary.py's XLA path (and its
// 3-D twin): per level, every unlabeled admitted pixel simultaneously
// adopts the MAX label among its neighbors from the previous state,
// repeated to convergence, then one final pass admits the whole mask.
// Labels are immutable once assigned, so the Jacobi fixpoint equals a
// breadth-first wave where a pixel joins at the first wave in which it
// has a labeled neighbor.  Phase 1 reads only pre-wave labels; phase 2
// commits, keeping same-wave assignments invisible exactly like the
// vectorized jnp.where update.
//
// Complexity: a PERSISTENT candidate set (unlabeled mask pixels adjacent
// to the labeled region) carries over between levels and admission is
// tested lazily per candidate, so there is exactly ONE full-image scan
// (candidate seeding) instead of the naive two per level — per-level
// cost is O(|boundary|), not O(n).  Every pixel enters the candidate
// list at most once per discovery edge, preserving the wave order: at a
// level's start ALL admitted candidates enter the first wave together,
// exactly the set the Jacobi step would label first.
template <typename Geo>
void watershed_levels_impl(const float* intensity, const int32_t* seeds,
                           const uint8_t* mask, size_t n, Geo geo,
                           const float* levels, int32_t n_levels,
                           int32_t* out) {
  std::vector<int32_t> labels(seeds, seeds + n);
  std::vector<uint8_t> in_cand(n, 0), in_next(n, 0);
  std::vector<int32_t> candidates, frontier, next, adopted;

  // the one full scan: unlabeled mask pixels touching the seeded region
  for (size_t i = 0; i < n; ++i) {
    if (labels[i] != 0 || !mask[i]) continue;
    bool touch = false;
    geo.for_neighbors((int32_t)i, [&](int32_t q) { touch |= labels[q] != 0; });
    if (touch) { candidates.push_back((int32_t)i); in_cand[i] = 1; }
  }

  auto flood_level = [&](auto admitted) {
    // admitted candidates form the first wave; the rest stay candidates
    frontier.clear();
    size_t keep = 0;
    for (size_t k = 0; k < candidates.size(); ++k) {
      const int32_t p = candidates[k];
      if (labels[p] != 0) { in_cand[p] = 0; continue; }  // labeled later on
      if (admitted(p)) {
        in_cand[p] = 0;
        frontier.push_back(p);
      } else {
        candidates[keep++] = p;
      }
    }
    candidates.resize(keep);
    while (!frontier.empty()) {
      adopted.assign(frontier.size(), 0);
      for (size_t k = 0; k < frontier.size(); ++k) {
        int32_t best = 0;
        geo.for_neighbors(frontier[k], [&](int32_t q) {
          best = std::max(best, labels[q]);
        });
        adopted[k] = best;  // >0 by frontier construction
      }
      next.clear();
      for (size_t k = 0; k < frontier.size(); ++k)
        labels[frontier[k]] = adopted[k];
      for (size_t k = 0; k < frontier.size(); ++k) {
        geo.for_neighbors(frontier[k], [&](int32_t q) {
          if (labels[q] != 0 || !mask[q]) return;
          if (admitted(q)) {
            // remaining candidates are all non-admitted at this level,
            // so an admitted unlabeled neighbor can only be fresh
            if (!in_next[q]) { in_next[q] = 1; next.push_back(q); }
          } else if (!in_cand[q]) {
            in_cand[q] = 1;
            candidates.push_back(q);  // for a later (dimmer) level
          }
        });
      }
      for (size_t k = 0; k < next.size(); ++k) in_next[next[k]] = 0;
      frontier.swap(next);
    }
  };

  for (int32_t l = 0; l < n_levels; ++l) {
    const float level = levels[l];
    flood_level([&](int32_t p) { return intensity[p] >= level; });
  }
  // mop up below the lowest level (numerical edge)
  flood_level([](int32_t) { return true; });
  for (size_t i = 0; i < n; ++i) out[i] = mask[i] ? labels[i] : 0;
}

}  // namespace wsnative

extern "C" {

// Fill background holes: background regions (connectivity-connected) not
// reachable from the image border become foreground.  Matches
// ops/label.py fill_holes (scipy binary_fill_holes semantics at the
// default background connectivity 4).  Returns 0, or -1 on bad args.
int32_t tm_fill_holes(const uint8_t* mask, int32_t h, int32_t w,
                      int32_t connectivity, uint8_t* out) {
  if (!mask || !out || h <= 0 || w <= 0) return -1;
  if (connectivity != 4 && connectivity != 8) return -1;
  const size_t n = (size_t)h * (size_t)w;
  std::vector<uint8_t> reached(n, 0);
  std::vector<int32_t> stack;
  auto push = [&](int32_t y, int32_t x) {
    if (y < 0 || y >= h || x < 0 || x >= w) return;
    const size_t i = (size_t)y * w + x;
    if (mask[i] || reached[i]) return;
    reached[i] = 1;
    stack.push_back((int32_t)i);
  };
  for (int32_t x = 0; x < w; ++x) { push(0, x); push(h - 1, x); }
  for (int32_t y = 0; y < h; ++y) { push(y, 0); push(y, w - 1); }
  while (!stack.empty()) {
    const int32_t i = stack.back();
    stack.pop_back();
    const int32_t y = i / w, x = i % w;
    push(y - 1, x); push(y + 1, x); push(y, x - 1); push(y, x + 1);
    if (connectivity == 8) {
      push(y - 1, x - 1); push(y - 1, x + 1);
      push(y + 1, x - 1); push(y + 1, x + 1);
    }
  }
  for (size_t i = 0; i < n; ++i) out[i] = mask[i] || !reached[i];
  return 0;
}

// Chessboard distance-to-background, matching ops/segment_primary.py
// distance_transform_approx's erosion-counting semantics: with
// K = min(max_distance, max chebyshev distance in the image) erosions
// executed, every foreground pixel reads min(d, K + 1).  The image border
// is NOT background (binary_erode pads with foreground).  Two-pass
// chamfer, O(n).  Returns 0, or -1 on bad args.
int32_t tm_chebyshev_dt(const uint8_t* mask, int32_t h, int32_t w,
                        int32_t max_distance, float* out) {
  if (!mask || !out || h <= 0 || w <= 0 || max_distance < 0) return -1;
  const size_t n = (size_t)h * (size_t)w;
  const int32_t INF = h + w + 2;  // > any chebyshev distance in-image
  std::vector<int32_t> d(n);
  for (size_t i = 0; i < n; ++i) d[i] = mask[i] ? INF : 0;
  auto relax = [&](size_t i, size_t j) {
    if (d[j] + 1 < d[i]) d[i] = d[j] + 1;
  };
  for (int32_t y = 0; y < h; ++y) {
    for (int32_t x = 0; x < w; ++x) {
      const size_t i = (size_t)y * w + x;
      if (!d[i]) continue;
      if (x > 0) relax(i, i - 1);
      if (y > 0) {
        relax(i, i - w);
        if (x > 0) relax(i, i - w - 1);
        if (x + 1 < w) relax(i, i - w + 1);
      }
    }
  }
  int32_t max_d = 0;
  for (int32_t y = h - 1; y >= 0; --y) {
    for (int32_t x = w - 1; x >= 0; --x) {
      const size_t i = (size_t)y * w + x;
      if (!d[i]) continue;
      if (x + 1 < w) relax(i, i + 1);
      if (y + 1 < h) {
        relax(i, i + w);
        if (x + 1 < w) relax(i, i + w + 1);
        if (x > 0) relax(i, i + w - 1);
      }
      max_d = std::max(max_d, d[i]);
    }
  }
  // no background anywhere -> nothing ever erodes (the erosion pads with
  // foreground), so the XLA loop runs all max_distance iterations and
  // every pixel reads max_distance + 1
  const int32_t K = (max_d >= INF) ? max_distance
                                   : std::min(max_distance, max_d);
  for (size_t i = 0; i < n; ++i) {
    // an unreachable pixel (no background at all) survives every erosion:
    // its distance is effectively infinite, not the INF sentinel value
    const int32_t di = (d[i] >= INF) ? K + 1 : d[i];
    out[i] = (float)std::min(di, K + 1) * (d[i] ? 1.0f : 0.0f);
  }
  return 0;
}

// Level-ordered watershed flooding, bit-identical to
// ops/segment_secondary.py watershed_from_seeds (XLA path): for each
// threshold in `levels` (descending), flood seed labels into mask pixels
// with intensity >= level to convergence (synchronous max-label
// adoption), then one final flood admitting the whole mask.  The caller
// passes the level values computed by the SAME jitted expression the XLA
// path uses, so band membership is decided by exact float comparisons.
// Returns 0, or -1 on bad args.
int32_t tm_watershed_levels(const float* intensity, const int32_t* seeds,
                            const uint8_t* mask, int32_t h, int32_t w,
                            const float* levels, int32_t n_levels,
                            int32_t connectivity, int32_t* out) {
  if (!intensity || !seeds || !mask || !out || h <= 0 || w <= 0) return -1;
  if (n_levels < 0 || (n_levels > 0 && !levels)) return -1;
  if (connectivity != 4 && connectivity != 8) return -1;
  const size_t n = (size_t)h * (size_t)w;
  wsnative::watershed_levels_impl(intensity, seeds, mask, n,
                                  wsnative::Geo2{h, w, connectivity},
                                  levels, n_levels, out);
  return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// 3-D CPU-fallback segmentation kernels (round-3): the z-stack twins of the
// 2-D kernels above, routed in by ops/volume.py when the backend is cpu
// (the 3-D lax.while_loop fixpoints are just as pathological on XLA-CPU as
// the 2-D ones were — volume bench sat at 0.77x the scipy baseline).

extern "C" {

// 3-D union-find connected components, scipy scan order (component ids by
// first voxel in (z, y, x) row-major order).  connectivity: 6 faces,
// 18 faces+edges, 26 full.  Returns N, or -1 on bad args.
int32_t tm_cc_label3d(const uint8_t* mask, int32_t nz, int32_t h, int32_t w,
                      int32_t connectivity, int32_t* out) {
  if (!mask || !out || nz <= 0 || h <= 0 || w <= 0) return -1;
  if (connectivity != 6 && connectivity != 18 && connectivity != 26) return -1;
  const size_t n = (size_t)nz * h * w;
  const int32_t plane = h * w;
  // prior-neighbor offsets: lexicographically negative (dz,dy,dx) kept by
  // connectivity class (1 nonzero = faces, <=2 = edges, <=3 = corners)
  std::vector<std::array<int32_t, 3>> offs;
  for (int32_t dz = -1; dz <= 1; ++dz)
    for (int32_t dy = -1; dy <= 1; ++dy)
      for (int32_t dx = -1; dx <= 1; ++dx) {
        if (dz > 0 || (dz == 0 && (dy > 0 || (dy == 0 && dx >= 0)))) continue;
        const int32_t nonzero = (dz != 0) + (dy != 0) + (dx != 0);
        if (connectivity == 6 && nonzero > 1) continue;
        if (connectivity == 18 && nonzero > 2) continue;
        offs.push_back({dz, dy, dx});
      }
  UnionFind uf(n);
  for (int32_t z = 0; z < nz; ++z) {
    for (int32_t y = 0; y < h; ++y) {
      for (int32_t x = 0; x < w; ++x) {
        const size_t i = (size_t)z * plane + (size_t)y * w + x;
        if (!mask[i]) continue;
        for (const auto& o : offs) {
          const int32_t zz = z + o[0], yy = y + o[1], xx = x + o[2];
          if (zz < 0 || yy < 0 || yy >= h || xx < 0 || xx >= w) continue;
          const size_t j = (size_t)zz * plane + (size_t)yy * w + xx;
          if (mask[j]) uf.unite((int32_t)i, (int32_t)j);
        }
      }
    }
  }
  std::vector<int32_t> remap(n, 0);
  int32_t nextid = 0;
  for (size_t i = 0; i < n; ++i) {
    if (!mask[i]) { out[i] = 0; continue; }
    const int32_t r = uf.find((int32_t)i);
    if (remap[r] == 0) remap[r] = ++nextid;
    out[i] = remap[r];
  }
  return nextid;
}

// 3-D level-ordered watershed flooding, bit-identical to
// ops/volume.py watershed_from_seeds_3d (26-neighbor synchronous-wave
// adoption per level, then a whole-mask mop-up).  Returns 0 / -1.
int32_t tm_watershed_levels3d(const float* intensity, const int32_t* seeds,
                              const uint8_t* mask, int32_t nz, int32_t h,
                              int32_t w, const float* levels,
                              int32_t n_levels, int32_t* out) {
  if (!intensity || !seeds || !mask || !out || nz <= 0 || h <= 0 || w <= 0)
    return -1;
  if (n_levels < 0 || (n_levels > 0 && !levels)) return -1;
  const size_t n = (size_t)nz * h * w;
  wsnative::watershed_levels_impl(intensity, seeds, mask, n,
                                  wsnative::Geo3{nz, h, w},
                                  levels, n_levels, out);
  return 0;
}

// Per-label intensity accumulators over a (possibly plate-scale) label
// mosaic in ONE pass: sum, sum-of-squares (float64 accumulation, exactly
// matching the numpy float64 bincount twin), min, max.  Arrays are sized
// count + 1 with index 0 = background.  Returns 0, or -1 on bad args /
// a label outside [0, count] (corrupt input must not scribble memory).
int32_t tm_mosaic_intensity(const int32_t* labels, const float* vals,
                            int64_t n, int32_t count, double* sum_out,
                            double* sq_out, double* min_out,
                            double* max_out) {
  if (!labels || !vals || !sum_out || !sq_out || !min_out || !max_out ||
      n < 0 || count < 0)
    return -1;
  const double inf = std::numeric_limits<double>::infinity();
  for (int32_t k = 0; k <= count; ++k) {
    sum_out[k] = 0.0;
    sq_out[k] = 0.0;
    min_out[k] = inf;
    max_out[k] = -inf;
  }
  for (int64_t i = 0; i < n; ++i) {
    const int32_t l = labels[i];
    if (l < 0 || l > count) return -1;
    const double v = static_cast<double>(vals[i]);
    sum_out[l] += v;
    sq_out[l] += v * v;
    if (v < min_out[l]) min_out[l] = v;
    if (v > max_out[l]) max_out[l] = v;
  }
  return 0;
}

// Per-label morphology accumulators over a label mosaic in ONE pass:
// pixel area, centroid sums, and bounding boxes.  Arrays sized count + 1
// (index 0 = background); ymin/xmin start at h/w and ymax/xmax at -1 so
// absent labels keep the numpy twin's sentinels.  Returns 0 / -1.
int32_t tm_mosaic_morph(const int32_t* labels, int32_t h, int32_t w,
                        int32_t count, int64_t* area_out, double* cy_out,
                        double* cx_out, int64_t* ymin_out, int64_t* ymax_out,
                        int64_t* xmin_out, int64_t* xmax_out) {
  if (!labels || !area_out || !cy_out || !cx_out || !ymin_out || !ymax_out ||
      !xmin_out || !xmax_out || h <= 0 || w <= 0 || count < 0)
    return -1;
  for (int32_t k = 0; k <= count; ++k) {
    area_out[k] = 0;
    cy_out[k] = 0.0;
    cx_out[k] = 0.0;
    ymin_out[k] = h;
    ymax_out[k] = -1;
    xmin_out[k] = w;
    xmax_out[k] = -1;
  }
  for (int32_t y = 0; y < h; ++y) {
    const int32_t* row = labels + static_cast<int64_t>(y) * w;
    for (int32_t x = 0; x < w; ++x) {
      const int32_t l = row[x];
      if (l < 0 || l > count) return -1;
      area_out[l] += 1;
      cy_out[l] += y;
      cx_out[l] += x;
      if (y < ymin_out[l]) ymin_out[l] = y;
      if (y > ymax_out[l]) ymax_out[l] = y;
      if (x < xmin_out[l]) xmin_out[l] = x;
      if (x > xmax_out[l]) xmax_out[l] = x;
    }
  }
  return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Round-5: per-SITE measurement accumulators.  The CPU backend's measure
// stage was scatter-bound (XLA-CPU lowers segment_sum/min/max to serial
// element scatters, ~2.3 ms/site at 256^2); one fused C pass computes all
// five per-label statistics for a whole site batch.

extern "C" {

// Per-label count / sum / sum-of-squares / min / max over a batch of label
// sites in ONE pass.  Accumulation is float32 in row-major pixel order —
// deliberately reproducing XLA-CPU's segment_sum/segment_min/segment_max
// (same adds, same order, multiply rounded before accumulate), so swapping
// the dispatch cannot move any downstream feature value.  Outputs are
// (n_sites, count + 1) row-major; index 0 = background; min/max start at
// +/-inf (the XLA reduction identities, kept for absent labels).  Labels
// outside [0, count] are DROPPED like the XLA scatter twin drops
// out-of-range segment ids (NOT an error: saturated sites legitimately
// carry clipped ids at the cap).  Returns 0, or -1 on null/negative args.
int32_t tm_site_stats(const int32_t* labels, const float* vals,
                      int64_t n_sites, int64_t px, int32_t count,
                      float* cnt_out, float* sum_out, float* sq_out,
                      float* min_out, float* max_out) {
  if (!labels || !vals || !cnt_out || !sum_out || !sq_out || !min_out ||
      !max_out || n_sites < 0 || px < 0 || count < 0)
    return -1;
  const float inf = std::numeric_limits<float>::infinity();
  const int64_t k1 = static_cast<int64_t>(count) + 1;
  for (int64_t s = 0; s < n_sites; ++s) {
    float* cnt = cnt_out + s * k1;
    float* sum = sum_out + s * k1;
    float* sq = sq_out + s * k1;
    float* mn = min_out + s * k1;
    float* mx = max_out + s * k1;
    for (int64_t k = 0; k < k1; ++k) {
      cnt[k] = 0.0f;
      sum[k] = 0.0f;
      sq[k] = 0.0f;
      mn[k] = inf;
      mx[k] = -inf;
    }
    const int32_t* lab = labels + s * px;
    const float* val = vals + s * px;
    for (int64_t i = 0; i < px; ++i) {
      const int32_t l = lab[i];
      if (l < 0 || l > count) continue;  // drop, like the XLA scatter
      const float x = val[i];
      const float xx = x * x;  // named temp: rounded, never fused (fma)
      cnt[l] += 1.0f;
      sum[l] += x;
      sq[l] += xx;
      if (x < mn[l]) mn[l] = x;
      if (x > mx[l]) mx[l] = x;
    }
  }
  return 0;
}

// Exact per-site histograms of int32 bin indices: counts accumulate as
// float32 (+1.0 adds are exact to 2^24 pixels/site); a negative index is
// normalized Python-style ONCE (+bins) and indices still out of range
// after that are dropped — all matching jnp's ``.at[idx].add`` scatter
// (ops/histogram.py method="scatter") bit-for-bit.  Outputs
// (n_sites, bins) row-major.  Returns 0 / -1 on null/invalid args.
int32_t tm_hist_counts(const int32_t* idx, int64_t n_sites, int64_t px,
                       int32_t bins, float* out) {
  if (!idx || !out || n_sites < 0 || px < 0 || bins <= 0) return -1;
  for (int64_t s = 0; s < n_sites; ++s) {
    float* row = out + s * static_cast<int64_t>(bins);
    for (int32_t b = 0; b < bins; ++b) row[b] = 0.0f;
    const int32_t* ix = idx + s * px;
    for (int64_t i = 0; i < px; ++i) {
      int32_t b = ix[i];
      if (b < 0) b += bins;  // jnp negative-index normalization
      if (b >= 0 && b < bins) row[b] += 1.0f;
    }
  }
  return 0;
}

}  // extern "C"

extern "C" {

// Fused per-site Otsu histogram: min/max plus the fixed-bin histogram of
// ((x - lo) / max(hi - lo, 1e-6)) * bins, truncated to int32 and clamped
// to [0, bins), in ONE pass over the pixels.  Every float operation is
// float32 with the same expression tree as the XLA path in
// ops/threshold.py otsu_value (sub, div, mul each rounded; int conversion
// truncates toward zero like XLA's ConvertElementType), and the build
// pins -ffp-contract=off, so the resulting histogram — and therefore the
// Otsu cut — is bit-identical.  Outputs: hist (n_sites, bins) float32,
// lo/hi (n_sites,) float32.  Returns 0 / -1 on bad args.
int32_t tm_otsu_hist(const float* img, int64_t n_sites, int64_t px,
                     int32_t bins, float* hist_out, float* lo_out,
                     float* hi_out) {
  if (!img || !hist_out || !lo_out || !hi_out || n_sites < 0 || px <= 0 ||
      bins <= 0)
    return -1;
  for (int64_t s = 0; s < n_sites; ++s) {
    const float* x = img + s * px;
    float lo = x[0], hi = x[0];
    for (int64_t i = 1; i < px; ++i) {
      if (x[i] < lo) lo = x[i];
      if (x[i] > hi) hi = x[i];
    }
    lo_out[s] = lo;
    hi_out[s] = hi;
    const float span_raw = hi - lo;
    const float span = span_raw > 1e-6f ? span_raw : 1e-6f;
    const float fbins = static_cast<float>(bins);
    float* hist = hist_out + s * static_cast<int64_t>(bins);
    for (int32_t b = 0; b < bins; ++b) hist[b] = 0.0f;
    for (int64_t i = 0; i < px; ++i) {
      const float a = x[i] - lo;     // each step rounded f32, like XLA
      const float r = a / span;
      const float c = r * fbins;
      int32_t b = static_cast<int32_t>(c);  // trunc toward zero
      if (b < 0) b = 0;
      if (b >= bins) b = bins - 1;
      hist[b] += 1.0f;
    }
  }
  return 0;
}

}  // extern "C"

extern "C" {

// Separable 2-D correlation over a batch of sites, bit-identical to the
// shifted-slice accumulation in ops/smooth.py _conv1d/uniform_smooth:
// per axis, out accumulates ky[i] * padded_slice_i with i ascending —
// each multiply rounded f32, each add rounded f32 (the build pins
// -ffp-contract=off), symmetric (numpy "symmetric") edge padding with
// ly/lx taps of pad on the leading side.  The kernels arrive as float32
// arrays COMPUTED BY the jitted caller, so there is no coefficient
// drift either.  Outputs (n_sites, h, w) float32.  Returns 0 / -1.
int32_t tm_sep_filter(const float* img, int64_t n_sites, int32_t h,
                      int32_t w, const float* ky, int32_t ny, int32_t ly,
                      const float* kx, int32_t nx, int32_t lx,
                      float* out) {
  if (!img || !ky || !kx || !out || n_sites < 0 || h <= 0 || w <= 0 ||
      ny <= 0 || nx <= 0 || ly < 0 || lx < 0 || ny - ly > h + 1 ||
      nx - lx > w + 1 || ly > h || lx > w)
    return -1;
  const int64_t px = static_cast<int64_t>(h) * w;
  std::vector<float> tmp(px);
  std::vector<float> row(static_cast<size_t>(w) + nx - 1);
  // numpy "symmetric": -1 -> 0, -2 -> 1, h -> h-1, h+1 -> h-2
  auto mirror = [](int32_t p, int32_t n) {
    if (p < 0) p = -p - 1;
    if (p >= n) p = 2 * n - 1 - p;
    return p;
  };
  for (int64_t s = 0; s < n_sites; ++s) {
    const float* in = img + s * px;
    // axis 0: tmp[y][x] = sum_i ky[i] * in[mirror(y + i - ly)][x]
    for (int32_t y = 0; y < h; ++y) {
      float* o = tmp.data() + static_cast<int64_t>(y) * w;
      for (int32_t x = 0; x < w; ++x) o[x] = 0.0f;
      for (int32_t i = 0; i < ny; ++i) {
        const float kv = ky[i];
        const float* src =
            in + static_cast<int64_t>(mirror(y + i - ly, h)) * w;
        for (int32_t x = 0; x < w; ++x) {
          const float prod = kv * src[x];  // rounded, never fused
          o[x] += prod;
        }
      }
    }
    // axis 1: out[y][x] = sum_i kx[i] * tmp[y][mirror(x + i - lx)]
    for (int32_t y = 0; y < h; ++y) {
      const float* t = tmp.data() + static_cast<int64_t>(y) * w;
      for (int32_t i = 0; i < nx - 1 + w; ++i)
        row[i] = t[mirror(i - lx, w)];
      float* o = out + s * px + static_cast<int64_t>(y) * w;
      for (int32_t x = 0; x < w; ++x) o[x] = 0.0f;
      for (int32_t i = 0; i < nx; ++i) {
        const float kv = kx[i];
        const float* src = row.data() + i;
        for (int32_t x = 0; x < w; ++x) {
          const float prod = kv * src[x];
          o[x] += prod;
        }
      }
    }
  }
  return 0;
}

}  // extern "C"

extern "C" {

// Separable box (mean) filter over a batch of sites, scipy
// uniform_filter semantics: per-axis running mean with "reflect"
// (numpy symmetric) borders, even windows biased one tap left, the
// axis-0 intermediate rounded to float32 like scipy's same-dtype
// intermediate.  O(1) work per pixel via double running sums (the
// unrolled XLA tap pass is O(size) — 31-tap windows dominated the
// adaptive-threshold module).  NOT bit-identical to the XLA taps
// (different accumulation order/precision) — threshold_adaptive's local
// mean is a tolerance-tier quantity, like the zernike host twin.
// Returns 0 / -1 on bad args (size must fit the image so a single
// mirror reflection covers the window).
int32_t tm_box_mean(const float* img, int64_t n_sites, int32_t h,
                    int32_t w, int32_t size, float* out) {
  if (!img || !out || n_sites < 0 || h <= 0 || w <= 0 || size <= 0 ||
      size > h || size > w)
    return -1;
  const int32_t left = size / 2;
  const int32_t right = size - left - 1;
  const double inv = 1.0 / static_cast<double>(size);
  const int64_t px = static_cast<int64_t>(h) * w;
  std::vector<float> tmp(px);
  std::vector<double> acc(w);
  auto mirror = [](int32_t p, int32_t n) {
    if (p < 0) p = -p - 1;
    if (p >= n) p = 2 * n - 1 - p;
    return p;
  };
  for (int64_t s = 0; s < n_sites; ++s) {
    const float* in = img + s * px;
    // axis 0: running column sums over the mirrored row window
    for (int32_t x = 0; x < w; ++x) acc[x] = 0.0;
    for (int32_t r = -left; r <= right; ++r) {
      const float* row = in + static_cast<int64_t>(mirror(r, h)) * w;
      for (int32_t x = 0; x < w; ++x) acc[x] += row[x];
    }
    for (int32_t y = 0; y < h; ++y) {
      float* t = tmp.data() + static_cast<int64_t>(y) * w;
      for (int32_t x = 0; x < w; ++x)
        t[x] = static_cast<float>(acc[x] * inv);
      if (y + 1 < h) {
        const float* add = in + static_cast<int64_t>(mirror(y + 1 + right, h)) * w;
        const float* sub = in + static_cast<int64_t>(mirror(y - left, h)) * w;
        for (int32_t x = 0; x < w; ++x) acc[x] += add[x] - sub[x];
      }
    }
    // axis 1: running sum along each (rounded) intermediate row
    for (int32_t y = 0; y < h; ++y) {
      const float* t = tmp.data() + static_cast<int64_t>(y) * w;
      float* o = out + s * px + static_cast<int64_t>(y) * w;
      double run = 0.0;
      for (int32_t c = -left; c <= right; ++c) run += t[mirror(c, w)];
      for (int32_t x = 0; x < w; ++x) {
        o[x] = static_cast<float>(run * inv);
        if (x + 1 < w)
          run += t[mirror(x + 1 + right, w)] - t[mirror(x - left, w)];
      }
    }
  }
  return 0;
}

}  // extern "C"

extern "C" {

// Multi-channel per-label sums over a batch of flattened sites:
// labels (n_sites, px) int32, vals (n_sites, n_channels, px) float32 →
// sums (n_sites, n_channels, count + 1) float32.  Accumulation is
// float32 in row-major pixel order per channel — XLA-CPU's
// segment_sum over (px, channels) stacks accumulates each channel
// column independently in pixel order, so this is bit-identical.
// Out-of-range labels are DROPPED like segment ids.  Returns 0 / -1.
int32_t tm_site_channel_sums(const int32_t* labels, const float* vals,
                             int64_t n_sites, int64_t n_channels,
                             int64_t px, int32_t count, float* sums_out) {
  if (!labels || !vals || !sums_out || n_sites < 0 || n_channels <= 0 ||
      px < 0 || count < 0)
    return -1;
  const int64_t k1 = static_cast<int64_t>(count) + 1;
  for (int64_t s = 0; s < n_sites; ++s) {
    const int32_t* lab = labels + s * px;
    for (int64_t c = 0; c < n_channels; ++c) {
      const float* v = vals + (s * n_channels + c) * px;
      float* out = sums_out + (s * n_channels + c) * k1;
      for (int64_t k = 0; k < k1; ++k) out[k] = 0.0f;
      for (int64_t i = 0; i < px; ++i) {
        const int32_t l = lab[i];
        if (l < 0 || l > count) continue;
        out[l] += v[i];
      }
    }
  }
  return 0;
}

// Multi-channel per-label (min, max), same layout/semantics as
// tm_site_channel_sums; absent labels keep the XLA reduction
// identities (+inf / -inf).  Returns 0 / -1.
int32_t tm_site_channel_minmax(const int32_t* labels, const float* vals,
                               int64_t n_sites, int64_t n_channels,
                               int64_t px, int32_t count, float* min_out,
                               float* max_out) {
  if (!labels || !vals || !min_out || !max_out || n_sites < 0 ||
      n_channels <= 0 || px < 0 || count < 0)
    return -1;
  const float inf = std::numeric_limits<float>::infinity();
  const int64_t k1 = static_cast<int64_t>(count) + 1;
  for (int64_t s = 0; s < n_sites; ++s) {
    const int32_t* lab = labels + s * px;
    for (int64_t c = 0; c < n_channels; ++c) {
      const float* v = vals + (s * n_channels + c) * px;
      float* mn = min_out + (s * n_channels + c) * k1;
      float* mx = max_out + (s * n_channels + c) * k1;
      for (int64_t k = 0; k < k1; ++k) {
        mn[k] = inf;
        mx[k] = -inf;
      }
      for (int64_t i = 0; i < px; ++i) {
        const int32_t l = lab[i];
        if (l < 0 || l > count) continue;
        const float x = v[i];
        if (x < mn[l]) mn[l] = x;
        if (x > mx[l]) mx[l] = x;
      }
    }
  }
  return 0;
}

}  // extern "C"

extern "C" {

// Per-object quantization + 4-direction GLCM accumulation in one native
// pass over a site batch.  Quantization replicates
// ops/measure.py quantize_per_object exactly: per-object min/max (pass
// 1), then floor(((v - lo) * (levels-1)) / max(span, 1e-6)) with each
// f32 step rounded separately (-ffp-contract=off) and clamped to
// [0, levels-1]; objects with no pixels never contribute.  GLCM counts
// are EXACT integers (f32 +1.0 adds, order-independent), accumulated
// for pixel pairs ((y, x), (y - dy, x - dx)) with equal nonzero labels
// — the same pairs ops/measure.py _glcm_scatter counts — and
// symmetrized (g + g^T).  Output layout:
// (n_sites, 4, count, levels, levels) float32, direction order
// (0,d), (d,0), (d,d), (d,-d).  Returns 0 / -1 on bad args.
int32_t tm_site_glcm(const int32_t* labels, const float* img,
                     int64_t n_sites, int32_t h, int32_t w, int32_t count,
                     int32_t levels, int32_t distance, float* glcm_out) {
  if (!labels || !img || !glcm_out || n_sites < 0 || h <= 0 || w <= 0 ||
      count < 0 || levels <= 1 || distance <= 0)
    return -1;
  const int64_t px = static_cast<int64_t>(h) * w;
  const int64_t ll = static_cast<int64_t>(levels) * levels;
  const int64_t per_site = 4 * static_cast<int64_t>(count) * ll;
  const float inf = std::numeric_limits<float>::infinity();
  std::vector<float> lo(count + 1), hi(count + 1);
  std::vector<uint8_t> q(px);
  const int32_t d = distance;
  const int32_t dys[4] = {0, d, d, d};
  const int32_t dxs[4] = {d, 0, d, -d};
  for (int64_t s = 0; s < n_sites; ++s) {
    const int32_t* lab = labels + s * px;
    const float* v = img + s * px;
    for (int32_t k = 0; k <= count; ++k) {
      lo[k] = inf;
      hi[k] = -inf;
    }
    for (int64_t i = 0; i < px; ++i) {
      const int32_t l = lab[i];
      if (l < 1 || l > count) continue;
      const float x = v[i];
      if (x < lo[l]) lo[l] = x;
      if (x > hi[l]) hi[l] = x;
    }
    // per-object stretch (quantize_per_object: lo=0/span=1 for absent,
    // span floor 1e-6; each op rounded f32)
    for (int64_t i = 0; i < px; ++i) {
      const int32_t l = lab[i];
      if (l < 1 || l > count) {
        q[i] = 0;  // background quantization is never read (pairs
                   // require equal labels > 0)
        continue;
      }
      const float present = hi[l] >= lo[l] ? 1.0f : 0.0f;
      const float lov = present ? lo[l] : 0.0f;
      const float span_raw = present ? (hi[l] - lov) : 1.0f;
      const float span = span_raw > 1e-6f ? span_raw : 1e-6f;
      const float a = v[i] - lov;
      const float b = a * static_cast<float>(levels - 1);
      const float c = b / span;
      float f = std::floor(c);
      // quantize_per_object holds the bin to floor's definition,
      // f*span <= b < (f+1)*span (the TPU's division can land one ulp
      // under a whole quotient); the same two f32 products here
      const float up = (f + 1.0f) * span;
      const float down = f * span;
      f = f + (up <= b ? 1.0f : 0.0f) - (down > b ? 1.0f : 0.0f);
      if (f < 0.0f) f = 0.0f;
      if (f > static_cast<float>(levels - 1))
        f = static_cast<float>(levels - 1);
      q[i] = static_cast<uint8_t>(f);
    }
    float* gsite = glcm_out + s * per_site;
    for (int64_t i = 0; i < per_site; ++i) gsite[i] = 0.0f;
    for (int32_t dir = 0; dir < 4; ++dir) {
      const int32_t dy = dys[dir], dx = dxs[dir];
      float* g = gsite + static_cast<int64_t>(dir) * count * ll;
      for (int32_t y = 0; y < h; ++y) {
        const int32_t y2 = y - dy;
        if (y2 < 0 || y2 >= h) continue;
        const int32_t x_begin = dx > 0 ? dx : 0;
        const int32_t x_end = dx < 0 ? w + dx : w;
        const int32_t* lrow = lab + static_cast<int64_t>(y) * w;
        const int32_t* lrow2 = lab + static_cast<int64_t>(y2) * w;
        const uint8_t* qrow = q.data() + static_cast<int64_t>(y) * w;
        const uint8_t* qrow2 = q.data() + static_cast<int64_t>(y2) * w;
        for (int32_t x = x_begin; x < x_end; ++x) {
          const int32_t l = lrow[x];
          if (l < 1 || l > count || lrow2[x - dx] != l) continue;
          g[(static_cast<int64_t>(l) - 1) * ll + qrow[x] * levels +
            qrow2[x - dx]] += 1.0f;
        }
      }
      // symmetrize in place: g = g + g^T per object
      for (int32_t k = 0; k < count; ++k) {
        float* gm = g + static_cast<int64_t>(k) * ll;
        for (int32_t i = 0; i < levels; ++i)
          for (int32_t j = i; j < levels; ++j) {
            const float sum = gm[i * levels + j] + gm[j * levels + i];
            gm[i * levels + j] = sum;
            gm[j * levels + i] = sum;
          }
      }
    }
  }
  return 0;
}

}  // extern "C"

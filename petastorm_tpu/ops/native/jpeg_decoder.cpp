// Native JPEG entropy decoder (stage 1 of the two-stage TPU decode): baseline
// (SOF0/SOF1) and progressive (SOF2).
//
// Huffman entropy decoding is sequential and branchy -- the one part of JPEG decode that
// cannot ride the TPU vector units -- so it runs on host as tight C++ instead of the
// pure-Python bit loop (petastorm_tpu/ops/jpeg.py entropy_decode_jpeg, the correctness
// oracle). Output contract is identical: per-component quantized DCT coefficient blocks
// in natural (unzigzagged) order plus natural-order quantization tables; stage 2
// (dequant + IDCT + upsample + color) runs on device as Pallas/XLA.
//
// Replaces the reference's cv2.imdecode host hot spot (petastorm/codecs.py ~L200) for the
// make_reader/make_batch_reader decode path; built by petastorm_tpu/ops/native/__init__.py
// with g++ at first use and called through ctypes (GIL released -> thread-pool parallel).
//
// Supports: 8-bit baseline sequential DCT (SOF0/SOF1, interleaved single scan) AND
// 8-bit progressive DCT (SOF2: DC/AC spectral selection, successive approximation,
// interleaved DC scans, per-component AC scans, EOB runs), 1..4 components, restart
// intervals, 0xFF00 byte stuffing. Rejects lossless/arithmetic/hierarchical modes.

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <system_error>
#include <thread>
#include <vector>

// Error codes (ptpu_jpeg_error_string maps them to messages)
enum {
  PTPU_JPEG_OK = 0,
  PTPU_JPEG_NOT_JPEG = -1,
  PTPU_JPEG_UNSUPPORTED_MODE = -2,
  PTPU_JPEG_CORRUPT = -3,
  PTPU_JPEG_NOT_8BIT = -4,
  PTPU_JPEG_BAD_COMPONENTS = -5,
  PTPU_JPEG_NO_SCAN = -6,
  PTPU_JPEG_LAYOUT_MISMATCH = -8,
};

namespace {

// zigzag scan position k -> natural (row-major u,v) index
const int kZigzagToNatural[64] = {
    0,  1,  8,  16, 9,  2,  3,  10, 17, 24, 32, 25, 18, 11, 4,  5,
    12, 19, 26, 33, 40, 48, 41, 34, 27, 20, 13, 6,  7,  14, 21, 28,
    35, 42, 49, 56, 57, 50, 43, 36, 29, 22, 15, 23, 30, 37, 44, 51,
    58, 59, 52, 45, 38, 31, 39, 46, 53, 60, 61, 54, 47, 55, 62, 63};

// The entropy-coded bytes and the read position. The bit buffer itself lives in the
// scan loops' locals: the low ``cnt`` bits of ``buf`` are the next bits, MSB first.
struct BitSrc {
  const uint8_t* data;
  int64_t len;
  int64_t pos;
  bool at_marker;  // hit 0xFF <marker>: stop consuming, pad zero bits (spec allows)

  // Skip to just past the next RSTn marker.
  void align_restart() {
    at_marker = false;
    while (pos + 1 < len) {
      if (data[pos] == 0xFF && data[pos + 1] >= 0xD0 && data[pos + 1] <= 0xD7) {
        pos += 2;
        return;
      }
      pos++;
    }
  }
};

struct Bits {
  uint64_t buf;
  int cnt;
};

// Byte-at-a-time refill to more than 56 bits: byte stuffing, and zeros once a marker
// (RSTn/EOI) or the end of the data is met. Out of line: the fast path below covers
// all but the bytes around a 0xFF.
__attribute__((noinline)) Bits refill_slow(BitSrc& s, Bits b) {
  while (b.cnt <= 56) {
    uint8_t byte = 0;
    if (!s.at_marker && s.pos < s.len) {
      byte = s.data[s.pos];
      if (byte == 0xFF) {
        uint8_t nxt = (s.pos + 1 < s.len) ? s.data[s.pos + 1] : 0xD9;
        if (nxt == 0x00) {
          s.pos += 2;  // byte-stuffed literal 0xFF
        } else {
          byte = 0;  // real marker: freeze pos, feed zeros
          s.at_marker = true;
        }
      } else {
        s.pos += 1;
      }
    }
    b.buf = (b.buf << 8) | byte;
    b.cnt += 8;
  }
  return b;
}

// Refill a buffer holding fewer than 32 bits to at least 56: 4-7 bytes in one load
// when none of the next 8 is 0xFF (the overwhelmingly common case mid-scan; a marker
// freezes ``pos`` on its 0xFF, so this never reads past one).
inline __attribute__((always_inline)) void refill(BitSrc& s, uint64_t& buf, int& cnt) {
  if (s.pos + 8 <= s.len) {
    uint64_t chunk;
    memcpy(&chunk, s.data + s.pos, 8);
    uint64_t t = ~chunk;  // a 0xFF byte becomes 0x00 in t
    if (!((t - 0x0101010101010101ULL) & ~t & 0x8080808080808080ULL)) {
      int take = (63 - cnt) >> 3;
      buf = (buf << (8 * take)) | (__builtin_bswap64(chunk) >> (64 - 8 * take));
      cnt += 8 * take;
      s.pos += take;
      return;
    }
  }
  Bits b = refill_slow(s, Bits{buf, cnt});
  buf = b.buf;
  cnt = b.cnt;
}

// The progressive scans' bit reader: the same buffer as a struct.
struct BitReader {
  BitSrc src;
  uint64_t buf;
  int cnt;

  void init(const uint8_t* d, int64_t l, int64_t p) {
    src = BitSrc{d, l, p, false};
    buf = 0;
    cnt = 0;
  }

  // One refill guard covers a full (code ≤16 bits, value ≤11 bits) coefficient read.
  inline void ensure28() {
    if (cnt < 28) refill(src, buf, cnt);
  }

  inline uint32_t peek16_raw() { return (uint32_t)((buf >> (cnt - 16)) & 0xFFFF); }

  // Consume n (≥1) already-buffered bits.
  inline int take(int n) {
    cnt -= n;
    return (int)((buf >> cnt) & ((1u << n) - 1));
  }

  void align_restart() {
    buf = 0;
    cnt = 0;
    src.align_restart();
  }
};

// JPEG EXTEND: map t-bit (t ≥ 1) magnitude to signed value, without a branch on the
// sign (it cannot be predicted): v - 2^t + 1 where v < 2^(t-1).
inline int extend(int v, int t) {
  return v + (((v - (1 << (t - 1))) >> 31) & (int)((~0u << t) + 1));
}

// Two-level Huffman decode: a 10-bit first-level LUT (2KiB, L1-resident — a flat
// 16-bit table is 128KiB/table and both costs a per-image build and misses L1 on the
// random peek pattern) plus the canonical mincode/maxcode/valptr fallback for the rare
// codes longer than 10 bits. LUT entry = (code_length << 8) | symbol; 0 = fallback.
//
// In front of it, ``fast``: one lookup of the next kFastBits bits resolves a code AND
// its magnitude bits whenever both fit (libjpeg-turbo's jdhuff.c and stb_image's
// fast_ac do the same). Entry = (value << 16) | (s << 12) | (run << 8) | bits, with
// ``value`` already EXTENDed, ``s`` its magnitude bits and ``bits`` the code length
// plus s; value 0 is a symbol with no magnitude (DC category 0, EOB, ZRL). 0 = take
// the two-level path.
struct HuffTable {
  static const int kLutBits = 10;
  static const int kFastBits = 10;
  uint16_t lut[1 << kLutBits];
  int32_t fast[1 << kFastBits];
  int32_t maxcode[17];  // per length: largest code value, or -1
  int32_t mincode[17];
  int32_t valptr[17];
  uint8_t symbols[256];
  // the DHT counts and symbols it was built from: the threads of a reader pool decode
  // stream after stream with the same tables (every cv2/libjpeg default-table
  // encode), which then cost a compare, not a build
  uint8_t spec[16 + 256];
  int spec_len;
  bool present;

  // ``ac``: symbols are (run << 4) | size with size ≤ 10 (else DC categories ≤ 11).
  void build(const uint8_t* counts, const uint8_t* syms, int total, bool ac) {
    present = true;
    if (spec_len == 16 + total && !memcmp(spec, counts, 16) &&
        !memcmp(spec + 16, syms, total))
      return;
    memset(lut, 0, sizeof(lut));
    memset(fast, 0, sizeof(fast));
    memcpy(symbols, syms, total);
    uint32_t code = 0;
    int k = 0;
    for (int length = 1; length <= 16; length++) {
      if (counts[length - 1]) {
        valptr[length] = k;
        mincode[length] = (int32_t)code;
        for (int i = 0; i < counts[length - 1]; i++, code++, k++) {
          // an over-subscribed (corrupt) table runs past its code space: such codes
          // are left to the canonical path, which never reads outside ``symbols``
          if (length > kLutBits || code >= (1u << length)) continue;
          uint32_t first = code << (kLutBits - length);
          uint32_t n = 1u << (kLutBits - length);
          uint16_t v = (uint16_t)((length << 8) | syms[k]);
          for (uint32_t j = 0; j < n; j++) lut[first + j] = v;
          add_fast(code, length, syms[k], ac);
        }
        maxcode[length] = (int32_t)code - 1;
      } else {
        maxcode[length] = -1;
      }
      code <<= 1;
    }
    memcpy(spec, counts, 16);
    memcpy(spec + 16, syms, total);
    spec_len = 16 + total;
  }

  void add_fast(uint32_t code, int length, uint8_t sym, bool ac) {
    if (length > kFastBits) return;
    int run = ac ? sym >> 4 : 0;
    int s = ac ? sym & 15 : sym;
    if (s > (ac ? 10 : 11)) return;  // corrupt: the two-level path reports it
    if (length + s > kFastBits) return;
    int free_bits = kFastBits - length;
    uint32_t first = code << free_bits;
    for (uint32_t j = 0; j < (1u << free_bits); j++) {
      int value = 0;
      if (s) value = extend((int)(j >> (free_bits - s)), s);
      fast[first + j] = (int32_t)(((uint32_t)value << 16) | (uint32_t)(s << 12) |
                                  (uint32_t)(run << 8) | (uint32_t)(length + s));
    }
  }

  // 16-bit peek → (length << 8) | symbol, or 0 on invalid code.
  inline uint32_t decode(uint32_t p16) const {
    uint32_t e = lut[p16 >> (16 - kLutBits)];
    if (e) return e;
    for (int l = kLutBits + 1; l <= 16; l++) {
      int32_t c = (int32_t)(p16 >> (16 - l));
      if (maxcode[l] >= 0 && c <= maxcode[l])
        return ((uint32_t)l << 8) | symbols[valptr[l] + (c - mincode[l])];
    }
    return 0;
  }
};

inline uint16_t be16(const uint8_t* p) { return (uint16_t)((p[0] << 8) | p[1]); }

// Frame component state shared by the baseline and progressive scan decoders.
struct JComp {
  int id, h, v, tq;
  int dc_tbl, ac_tbl;
};

// One progressive scan (ITU-T T.81 §G): DC or AC band, first pass or successive-
// approximation refinement. Coefficients accumulate into blocks16[c] (the padded
// interleaved grid, stride out_bx[c]); scans arrive in any spec-legal order.
// On success sets *end_pos to the next marker after the entropy-coded data.
// Returns a PTPU_JPEG_* code.
struct ProgScanArgs {
  const uint8_t* data;
  int64_t len;
  int64_t start;
  const JComp* comps;
  const int* scan_comps;  // indices into comps, scan order
  int ns;
  int Ss, Se, Ah, Al;
  const HuffTable* huff_dc;  // [4]
  const HuffTable* huff_ac;  // [4]
  int restart_interval;
  int width, height, hmax, vmax, mcus_x, mcus_y;
  int16_t* const* blocks;   // per frame component c: padded grid
  const int* out_bx;        // per frame component: padded blocks_x (row stride)
};

inline int prog_find_next_marker(const uint8_t* data, int64_t len, int64_t from,
                                 int64_t* out) {
  for (int64_t p = from; p + 1 < len; p++) {
    if (data[p] == 0xFF && data[p + 1] != 0x00 &&
        !(data[p + 1] >= 0xD0 && data[p + 1] <= 0xD7))
      {
        *out = p;
        return PTPU_JPEG_OK;
      }
  }
  *out = len;
  return PTPU_JPEG_OK;  // no further marker: treated as end of stream
}

int decode_progressive_scan(const ProgScanArgs& a, int64_t* end_pos) {
  BitReader br;
  br.init(a.data, a.len, a.start);
  int pred[4] = {0, 0, 0, 0};
  int eobrun = 0;
  const int p1 = 1 << a.Al;
  const int m1 = -(1 << a.Al);
  int mcu_count = 0;

  auto restart_check = [&]() {
    if (a.restart_interval && mcu_count && mcu_count % a.restart_interval == 0) {
      br.align_restart();
      pred[0] = pred[1] = pred[2] = pred[3] = 0;
      eobrun = 0;
    }
  };

  if (a.Ss == 0) {
    // ---- DC scan (Se must be 0) ----
    if (a.Se != 0) return PTPU_JPEG_CORRUPT;
    if (a.ns > 1) {
      // interleaved DC scan: full-frame MCU traversal over the scan components
      for (int my = 0; my < a.mcus_y; my++) {
        for (int mx = 0; mx < a.mcus_x; mx++) {
          restart_check();
          for (int s = 0; s < a.ns; s++) {
            int c = a.scan_comps[s];
            const JComp& comp = a.comps[c];
            for (int v = 0; v < comp.v; v++) {
              for (int hh = 0; hh < comp.h; hh++) {
                int brow = my * comp.v + v;
                int bcol = mx * comp.h + hh;
                int16_t* blk = a.blocks[c] + ((size_t)brow * a.out_bx[c] + bcol) * 64;
                br.ensure28();
                if (a.Ah == 0) {
                  uint32_t e = a.huff_dc[comp.dc_tbl].decode(br.peek16_raw());
                  if (!e) return PTPU_JPEG_CORRUPT;
                  br.cnt -= e >> 8;
                  int t = e & 0xFF;
                  if (t > 11) return PTPU_JPEG_CORRUPT;
                  if (t) pred[c] += extend(br.take(t), t);
                  blk[0] = (int16_t)(pred[c] * p1);  // value << Al
                } else {
                  if (br.take(1)) blk[0] = (int16_t)(blk[0] | p1);
                }
              }
            }
          }
          mcu_count++;
        }
      }
    } else {
      // single-component DC scan: non-interleaved block geometry
      int c = a.scan_comps[0];
      const JComp& comp = a.comps[c];
      int cw = (a.width * comp.h + a.hmax - 1) / a.hmax;   // ceil(X * Hi / Hmax)
      int ch = (a.height * comp.v + a.vmax - 1) / a.vmax;
      int wb = (cw + 7) / 8, hb = (ch + 7) / 8;
      for (int brow = 0; brow < hb; brow++) {
        for (int bcol = 0; bcol < wb; bcol++) {
          restart_check();
          int16_t* blk = a.blocks[c] + ((size_t)brow * a.out_bx[c] + bcol) * 64;
          br.ensure28();
          if (a.Ah == 0) {
            uint32_t e = a.huff_dc[comp.dc_tbl].decode(br.peek16_raw());
            if (!e) return PTPU_JPEG_CORRUPT;
            br.cnt -= e >> 8;
            int t = e & 0xFF;
            if (t > 11) return PTPU_JPEG_CORRUPT;
            if (t) pred[c] += extend(br.take(t), t);
            blk[0] = (int16_t)(pred[c] * p1);
          } else {
            if (br.take(1)) blk[0] = (int16_t)(blk[0] | p1);
          }
          mcu_count++;
        }
      }
    }
  } else {
    // ---- AC scan: always single-component (T.81 §G.1.1.1.1) ----
    if (a.ns != 1) return PTPU_JPEG_UNSUPPORTED_MODE;
    if (a.Se > 63 || a.Ss > a.Se) return PTPU_JPEG_CORRUPT;
    int c = a.scan_comps[0];
    const JComp& comp = a.comps[c];
    const HuffTable& ac = a.huff_ac[comp.ac_tbl];
    int cw = (a.width * comp.h + a.hmax - 1) / a.hmax;
    int ch = (a.height * comp.v + a.vmax - 1) / a.vmax;
    int wb = (cw + 7) / 8, hb = (ch + 7) / 8;
    for (int brow = 0; brow < hb; brow++) {
      for (int bcol = 0; bcol < wb; bcol++) {
        restart_check();
        int16_t* blk = a.blocks[c] + ((size_t)brow * a.out_bx[c] + bcol) * 64;
        if (a.Ah == 0) {
          // first pass over this band
          if (eobrun > 0) {
            eobrun--;
          } else {
            int k = a.Ss;
            while (k <= a.Se) {
              br.ensure28();
              uint32_t e = ac.decode(br.peek16_raw());
              if (!e) return PTPU_JPEG_CORRUPT;
              br.cnt -= e >> 8;
              int r = (e & 0xFF) >> 4, s = e & 0xF;
              if (s == 0) {
                if (r != 15) {
                  eobrun = (1 << r) - 1;
                  if (r) {
                    br.ensure28();
                    eobrun += br.take(r);
                  }
                  break;  // end of band for this block
                }
                k += 16;
              } else {
                if (s > 10) return PTPU_JPEG_CORRUPT;
                k += r;
                if (k > a.Se) return PTPU_JPEG_CORRUPT;
                blk[kZigzagToNatural[k]] = (int16_t)(extend(br.take(s), s) * p1);
                k++;
              }
            }
          }
        } else {
          // refinement pass (libjpeg jdphuff.c decode_mcu_AC_refine structure)
          int k = a.Ss;
          if (eobrun == 0) {
            while (k <= a.Se) {
              br.ensure28();
              uint32_t e = ac.decode(br.peek16_raw());
              if (!e) return PTPU_JPEG_CORRUPT;
              br.cnt -= e >> 8;
              int r = (e & 0xFF) >> 4, s = e & 0xF;
              int newval = 0;
              if (s == 0) {
                if (r != 15) {
                  // NOT (1<<r)-1: the tail handler below consumes the current
                  // block's remaining correction bits and decrements (libjpeg
                  // decode_mcu_AC_refine); with the -1 form an r==0 EOB would skip
                  // those bits and desynchronize the stream
                  eobrun = (1 << r);
                  if (r) {
                    br.ensure28();
                    eobrun += br.take(r);
                  }
                  break;  // tail correction below consumes the rest of the band
                }
                // r == 15: skip over 16 zero-history coefficients
              } else {
                if (s != 1) return PTPU_JPEG_CORRUPT;
                br.ensure28();
                newval = br.take(1) ? p1 : m1;
              }
              while (k <= a.Se) {
                int16_t* cf = blk + kZigzagToNatural[k];
                if (*cf != 0) {
                  br.ensure28();
                  if (br.take(1) && (*cf & p1) == 0)
                    *cf = (int16_t)(*cf + (*cf >= 0 ? p1 : m1));
                } else {
                  if (r == 0) break;
                  r--;
                }
                k++;
              }
              if (s && k <= a.Se) blk[kZigzagToNatural[k]] = (int16_t)newval;
              k++;
            }
          }
          if (eobrun > 0) {
            // correction bits for the remaining nonzero history in the band
            while (k <= a.Se) {
              int16_t* cf = blk + kZigzagToNatural[k];
              if (*cf != 0) {
                br.ensure28();
                if (br.take(1) && (*cf & p1) == 0)
                  *cf = (int16_t)(*cf + (*cf >= 0 ? p1 : m1));
              }
              k++;
            }
            eobrun--;
          }
        }
        mcu_count++;
      }
    }
  }
  return prog_find_next_marker(a.data, a.len, br.src.pos > a.start ? br.src.pos : a.start,
                               end_pos);
}

// -- stage-2 input packing -------------------------------------------------------
// A block row's value at shipped position j: zigzag position j of a natural-order
// row (kZig), or the row's j-th value as stored.
template <bool kZig>
inline int16_t shipped_at(const int16_t* s, int32_t j) {
  return kZig ? s[kZigzagToNatural[j]] : s[j];
}

// Two values as one 12-bit pair in 3 bytes (ptpu_jpeg_pack12's layout); false when
// either lies outside the 12-bit range. The range checks branch: they are all but
// always taken the same way, and measured faster than branch-free checks.
inline bool put12(uint8_t* d, int16_t a, int16_t b) {
  if (a < -2048 || a > 2047 || b < -2048 || b > 2047) return false;
  uint16_t ua = (uint16_t)a & 0xFFF;
  uint16_t ub = (uint16_t)b & 0xFFF;
  d[0] = (uint8_t)(ua & 0xFF);
  d[1] = (uint8_t)(((ua >> 8) & 0xF) | ((ub & 0xF) << 4));
  d[2] = (uint8_t)((ub >> 4) & 0xFF);
  return true;
}

// Each of nblocks block rows of src (``stride`` values apart), positions [0, k):
// copy_blocks writes them as int16, pack12_blocks as 12-bit pairs (k even; -1 on a
// range failure), split_blocks as ptpu_jpeg_pack_split's three slabs.
template <bool kZig>
void copy_blocks(const int16_t* src, int64_t stride, int64_t nblocks, int32_t k,
                 int16_t* dst) {
  for (int64_t b = 0; b < nblocks; b++) {
    const int16_t* s = src + b * stride;
    int16_t* d = dst + b * k;
    for (int32_t j = 0; j < k; j++) d[j] = shipped_at<kZig>(s, j);
  }
}

template <bool kZig>
int32_t pack12_blocks(const int16_t* src, int64_t stride, int64_t nblocks, int32_t k,
                      uint8_t* dst) {
  const int64_t out_stride = (int64_t)(k / 2) * 3;
  for (int64_t b = 0; b < nblocks; b++) {
    const int16_t* s = src + b * stride;
    uint8_t* d = dst + b * out_stride;
    for (int32_t j = 0; j < k; j += 2)
      if (!put12(d + (j / 2) * 3, shipped_at<kZig>(s, j), shipped_at<kZig>(s, j + 1)))
        return -1;
  }
  return 0;
}

// Kept out of line: inlined into ptpu_jpeg_pack_rows' row loop it ran slower.
template <bool kZig>
__attribute__((noinline)) int32_t split_blocks(const int16_t* src, int64_t stride,
                                               int64_t nblocks, int32_t k, int32_t k1,
                                               int32_t k2, uint8_t* head, int8_t* mid,
                                               uint8_t* tail) {
  const int64_t head_stride = (int64_t)(k1 / 2) * 3;
  const int64_t mid_stride = k2 - k1;
  const int64_t tail_stride = (k - k2) / 2;
  for (int64_t b = 0; b < nblocks; b++) {
    const int16_t* s = src + b * stride;
    uint8_t* hd = head + b * head_stride;
    int8_t* md = mid + b * mid_stride;
    uint8_t* tl = tail + b * tail_stride;
    for (int32_t j = 0; j < k1; j += 2)
      if (!put12(hd + (j / 2) * 3, shipped_at<kZig>(s, j), shipped_at<kZig>(s, j + 1)))
        return -1;
    for (int32_t j = k1; j < k2; j++) {
      int16_t a = shipped_at<kZig>(s, j);
      if (a < -128 || a > 127) return -2;
      md[j - k1] = (int8_t)a;
    }
    for (int32_t j = k2; j < k; j += 2) {
      int16_t a = shipped_at<kZig>(s, j), c = shipped_at<kZig>(s, j + 1);
      if (a < -8 || a > 7 || c < -8 || c > 7) return -3;
      tl[(j - k2) / 2] = (uint8_t)(((uint8_t)a & 0xF) | (((uint8_t)c & 0xF) << 4));
    }
  }
  return 0;
}

// One component of ptpu_jpeg_pack_rows (see there): its rows, variant and slabs.
struct PackComp {
  const int16_t* const* rows;
  int64_t nblocks;
  int32_t k, mode, k1, k2;
  void* out0;
  int8_t* out1;
  uint8_t* out2;
};

int32_t pack_row(const PackComp a, int64_t i) {
  const int16_t* s = a.rows[i];
  const int64_t nb = a.nblocks;
  const int32_t k = a.k;
  const bool zig = a.mode == 2 || k < 64;
  if (a.mode == 0) {
    int16_t* d = (int16_t*)a.out0 + i * nb * k;
    if (zig)
      copy_blocks<true>(s, 64, nb, k, d);
    else
      memcpy(d, s, (size_t)nb * 64 * sizeof(int16_t));
    return 0;
  }
  if (a.mode == 1) {
    uint8_t* d = (uint8_t*)a.out0 + i * nb * (k / 2) * 3;
    return zig ? pack12_blocks<true>(s, 64, nb, k, d)
               : pack12_blocks<false>(s, 64, nb, k, d);
  }
  return split_blocks<true>(s, 64, nb, k, a.k1, a.k2,
                            (uint8_t*)a.out0 + i * nb * (a.k1 / 2) * 3,
                            a.out1 + i * nb * (a.k2 - a.k1),
                            a.out2 + i * nb * ((k - a.k2) / 2));
}

// -- baseline scan --------------------------------------------------------------

// What one stream's decode gives besides its coefficients and quantization tables.
struct StreamStats {
  int32_t specmax[4][64];  // per component and ZIGZAG position: the largest |value|
  int64_t symbols;         // Huffman symbols decoded
  int64_t fast_symbols;    // ... of them resolved, magnitude bits included, by
                           // HuffTable::fast's one lookup
};

// One baseline block, DC then AC, into ``blk`` (zeroed here), folding each value's
// magnitude into the component's zigzag profile ``sm``. The bit buffer is the
// caller's locals. False on an invalid code or magnitude category.
inline __attribute__((always_inline)) bool decode_block(
    BitSrc& src, uint64_t& buf, int& cnt, const HuffTable& dc, const HuffTable& ac,
    int& pred, int16_t* blk, int32_t* sm, int64_t& nsym, int64_t& nfast) {
  const int kFast = HuffTable::kFastBits;
  const uint64_t kMask = (1u << kFast) - 1;
  memset(blk, 0, 64 * sizeof(int16_t));
  // DC: code ≤16 + magnitude ≤11 bits
  if (cnt < 28) refill(src, buf, cnt);
  nsym++;
  int32_t fe = dc.fast[(buf >> (cnt - kFast)) & kMask];
  if (fe) {
    nfast++;
    cnt -= fe & 31;
    pred += fe >> 16;
  } else {
    uint32_t e = dc.decode((uint32_t)(buf >> (cnt - 16)) & 0xFFFF);
    if (!e) return false;
    cnt -= e >> 8;
    int t = e & 0xFF;
    if (t > 11) return false;  // 8-bit baseline DC category ≤ 11; larger → corrupt DHT
    if (t) {
      cnt -= t;
      pred += extend((int)(buf >> cnt) & ((1 << t) - 1), t);
    }
  }
  int16_t d = (int16_t)pred;
  blk[0] = d;
  sm[0] = std::max(sm[0], (int32_t)(d < 0 ? -d : d));
  // AC: code ≤16 + magnitude ≤10 bits
  int k = 1;
  while (k < 64) {
    if (cnt < 26) refill(src, buf, cnt);
    nsym++;
    fe = ac.fast[(buf >> (cnt - kFast)) & kMask];
    int v;
    if (fe) {
      nfast++;
      cnt -= fe & 31;
      v = fe >> 16;
      int r = (fe >> 8) & 15;
      if (v == 0) {  // EOB, or ZRL (16 zeros)
        if (r != 15) break;
        k += 16;
        continue;
      }
      k += r;
      if (k > 63) {  // run past the block: its magnitude bits stay unread
        cnt += (fe >> 12) & 15;
        break;
      }
    } else {
      uint32_t e = ac.decode((uint32_t)(buf >> (cnt - 16)) & 0xFFFF);
      if (!e) return false;
      cnt -= e >> 8;
      int r = (e >> 4) & 15, s = e & 15;
      if (s == 0) {
        if (r != 15) break;
        k += 16;
        continue;
      }
      if (s > 10) return false;  // 8-bit baseline AC size ≤ 10
      k += r;
      if (k > 63) break;
      cnt -= s;
      v = extend((int)(buf >> cnt) & ((1 << s) - 1), s);
    }
    blk[kZigzagToNatural[k]] = (int16_t)v;
    sm[k] = std::max(sm[k], v < 0 ? -v : v);
    k++;
  }
  return true;
}

// The single interleaved scan of a baseline frame, every block of the padded MCU
// grid (so each is zeroed and written here: the buffers need no clearing first).
int decode_baseline_scan(BitSrc src, const JComp* comps, int ncomp,
                         const HuffTable* huff_dc, const HuffTable* huff_ac,
                         int restart_interval, int mcus_x, int mcus_y,
                         int16_t* const* blocks, const int32_t* blocks_x,
                         StreamStats* st) {
  uint64_t buf = 0;
  int cnt = 0;
  int pred[4] = {0, 0, 0, 0};
  int64_t nsym = 0, nfast = 0;
  int to_restart = restart_interval;
  for (int my = 0; my < mcus_y; my++) {
    for (int mx = 0; mx < mcus_x; mx++) {
      if (restart_interval) {
        if (to_restart == 0) {
          buf = 0;
          cnt = 0;
          src.align_restart();
          pred[0] = pred[1] = pred[2] = pred[3] = 0;
          to_restart = restart_interval;
        }
        to_restart--;
      }
      for (int c = 0; c < ncomp; c++) {
        const JComp& comp = comps[c];
        const HuffTable& dc = huff_dc[comp.dc_tbl];
        const HuffTable& ac = huff_ac[comp.ac_tbl];
        for (int v = 0; v < comp.v; v++) {
          int16_t* blk = blocks[c] + ((size_t)(my * comp.v + v) * blocks_x[c] +
                                      (size_t)mx * comp.h) * 64;
          for (int hh = 0; hh < comp.h; hh++, blk += 64) {
            if (!decode_block(src, buf, cnt, dc, ac, pred[c], blk, st->specmax[c],
                              nsym, nfast)) {
              st->symbols += nsym;
              st->fast_symbols += nfast;
              return PTPU_JPEG_CORRUPT;
            }
          }
        }
      }
    }
  }
  st->symbols += nsym;
  st->fast_symbols += nfast;
  return PTPU_JPEG_OK;
}

// Per-zigzag-position max |coefficient| of nblocks natural-order blocks, into out.
void fold_specmax(const int16_t* src, int64_t nblocks, int32_t* out) {
  for (int64_t b = 0; b < nblocks; b++) {
    const int16_t* s = src + b * 64;
    for (int32_t j = 0; j < 64; j++) {
      int32_t v = s[kZigzagToNatural[j]];
      out[j] = std::max(out[j], v < 0 ? -v : v);
    }
  }
}

}  // namespace

extern "C" {

// Decode layout: everything that shapes the stacked coefficient buffers (the batch
// API requires every stream in a batch to share one).
typedef struct {
  int32_t height;
  int32_t width;
  int32_t ncomp;
  int32_t h_samp[4];
  int32_t v_samp[4];
  int32_t blocks_y[4];
  int32_t blocks_x[4];
} PtpuJpegLayout;

const char* ptpu_jpeg_error_string(int code) {
  switch (code) {
    case PTPU_JPEG_OK: return "ok";
    case PTPU_JPEG_NOT_JPEG: return "Not a JPEG (missing SOI)";
    case PTPU_JPEG_UNSUPPORTED_MODE:
      return "Unsupported JPEG mode (lossless/arithmetic/non-interleaved-baseline)";
    case PTPU_JPEG_CORRUPT: return "Corrupt JPEG stream";
    case PTPU_JPEG_NOT_8BIT: return "Only 8-bit baseline JPEG supported";
    case PTPU_JPEG_BAD_COMPONENTS: return "Unsupported component count/sampling";
    case PTPU_JPEG_NO_SCAN: return "No SOS marker found";
    case PTPU_JPEG_LAYOUT_MISMATCH:
      return "JPEG layout differs from the batch layout";
    default: return "Unknown error";
  }
}

// Core decoder: one stream into the caller's buffers (dst[c]: blocks_y*blocks_x*64
// int16 each, natural order; qdst: ncomp*64 int32 natural order) after verifying its
// layout equals ``expect``. ``st`` gets the stream's zigzag profile (``specmax``, the
// largest |value| at each position: the highest nonzero one is how far the caller
// must ship, and the profile picks the bit widths it ships in) and its symbol counts.
static int decode_impl(const uint8_t* data, int64_t len, const PtpuJpegLayout* expect,
                       int16_t* const* dst, int32_t* qdst, StreamStats* st) {
  memset(st, 0, sizeof(*st));
  if (len < 4 || data[0] != 0xFF || data[1] != 0xD8) return PTPU_JPEG_NOT_JPEG;

  int32_t qt_zz[4][64];  // DQT tables in zigzag order as parsed
  bool qt_present[4] = {false, false, false, false};
  // ~50KiB; off-stack, re-entrant, and kept from stream to stream (HuffTable::build)
  static thread_local HuffTable huff_dc[4], huff_ac[4];
  for (int i = 0; i < 4; i++) {
    huff_dc[i].present = false;
    huff_ac[i].present = false;
  }

  JComp comps[4];
  int ncomp = 0;
  int height = 0, width = 0;
  bool have_frame = false;
  bool progressive = false;
  bool allocated = false;
  int scans_done = 0;
  int hmax = 1, vmax = 1, mcus_x = 0, mcus_y = 0;
  int restart_interval = 0;

  int64_t pos = 2;
  int rc = PTPU_JPEG_NO_SCAN;

  while (pos < len) {
    if (data[pos] != 0xFF) {
      pos++;
      continue;
    }
    if (pos + 1 >= len) break;
    uint8_t marker = data[pos + 1];
    pos += 2;
    if (marker == 0xD8 || marker == 0x01 || (marker >= 0xD0 && marker <= 0xD7)) continue;
    if (marker == 0xD9) break;  // EOI
    if (pos + 2 > len) {
      rc = PTPU_JPEG_CORRUPT;
      break;
    }
    int seglen = be16(data + pos);
    if (seglen < 2 || pos + seglen > len) {
      rc = PTPU_JPEG_CORRUPT;
      break;
    }
    const uint8_t* seg = data + pos + 2;
    int segbytes = seglen - 2;

    if (marker == 0xDB) {  // DQT
      int s = 0;
      while (s < segbytes) {
        int pq = seg[s] >> 4, tq = seg[s] & 0xF;
        s += 1;
        if (tq > 3) {
          rc = PTPU_JPEG_CORRUPT;
          goto done;
        }
        if (pq) {
          if (s + 128 > segbytes) {
            rc = PTPU_JPEG_CORRUPT;
            goto done;
          }
          for (int i = 0; i < 64; i++) qt_zz[tq][i] = be16(seg + s + 2 * i);
          s += 128;
        } else {
          if (s + 64 > segbytes) {
            rc = PTPU_JPEG_CORRUPT;
            goto done;
          }
          for (int i = 0; i < 64; i++) qt_zz[tq][i] = seg[s + i];
          s += 64;
        }
        qt_present[tq] = true;
      }
    } else if (marker == 0xC0 || marker == 0xC1 || marker == 0xC2) {
      // SOF0/SOF1 baseline, SOF2 progressive
      if (segbytes < 6) {
        rc = PTPU_JPEG_CORRUPT;
        goto done;
      }
      int precision = seg[0];
      if (precision != 8) {
        rc = PTPU_JPEG_NOT_8BIT;
        goto done;
      }
      if (have_frame) {
        // a second frame header is illegal (T.81: one frame per non-hierarchical
        // stream) and would re-derive geometry the coefficient buffers no longer
        // match — reject instead of writing through stale pointers/strides
        rc = PTPU_JPEG_CORRUPT;
        goto done;
      }
      progressive = (marker == 0xC2);
      height = be16(seg + 1);
      width = be16(seg + 3);
      ncomp = seg[5];
      if (ncomp < 1 || ncomp > 4 || segbytes < 6 + 3 * ncomp) {
        rc = PTPU_JPEG_BAD_COMPONENTS;
        goto done;
      }
      for (int i = 0; i < ncomp; i++) {
        comps[i].id = seg[6 + 3 * i];
        comps[i].h = seg[7 + 3 * i] >> 4;
        comps[i].v = seg[7 + 3 * i] & 0xF;
        comps[i].tq = seg[8 + 3 * i];
        if (comps[i].h < 1 || comps[i].h > 4 || comps[i].v < 1 || comps[i].v > 4 ||
            comps[i].tq > 3) {
          rc = PTPU_JPEG_BAD_COMPONENTS;
          goto done;
        }
        if (comps[i].h > hmax) hmax = comps[i].h;
        if (comps[i].v > vmax) vmax = comps[i].v;
      }
      mcus_x = (width + 8 * hmax - 1) / (8 * hmax);
      mcus_y = (height + 8 * vmax - 1) / (8 * vmax);
      have_frame = true;
    } else if (marker == 0xC4) {  // DHT
      int s = 0;
      while (s + 17 <= segbytes) {
        int tc = seg[s] >> 4, th = seg[s] & 0xF;
        if (th > 3 || tc > 1) {
          rc = PTPU_JPEG_CORRUPT;
          goto done;
        }
        const uint8_t* counts = seg + s + 1;
        int total = 0;
        for (int i = 0; i < 16; i++) total += counts[i];
        if (s + 17 + total > segbytes) {
          rc = PTPU_JPEG_CORRUPT;
          goto done;
        }
        if (tc == 0)
          huff_dc[th].build(counts, seg + s + 17, total, false);
        else
          huff_ac[th].build(counts, seg + s + 17, total, true);
        s += 17 + total;
      }
    } else if (marker == 0xDD) {  // DRI
      if (segbytes < 2) {
        rc = PTPU_JPEG_CORRUPT;
        goto done;
      }
      restart_interval = be16(seg);
    } else if (marker == 0xC3 || marker == 0xC5 || marker == 0xC6 ||
               marker == 0xC7 || marker == 0xC9 || marker == 0xCA || marker == 0xCB ||
               marker == 0xCD || marker == 0xCE || marker == 0xCF) {
      rc = PTPU_JPEG_UNSUPPORTED_MODE;  // lossless / arithmetic / hierarchical
      goto done;
    } else if (marker == 0xDA) {  // SOS
      if (!have_frame || segbytes < 1) {
        rc = PTPU_JPEG_CORRUPT;
        goto done;
      }
      int ns = seg[0];
      if (ns < 1 || ns > 4 || segbytes < 1 + 2 * ns + 3) {
        rc = PTPU_JPEG_CORRUPT;
        goto done;
      }
      int scan_comps[4];
      for (int i = 0; i < ns; i++) {
        int cs = seg[1 + 2 * i];
        int found = -1;
        for (int c = 0; c < ncomp; c++)
          if (comps[c].id == cs) found = c;
        if (found < 0) {
          rc = PTPU_JPEG_CORRUPT;
          goto done;
        }
        int td = seg[2 + 2 * i] >> 4;
        int ta = seg[2 + 2 * i] & 0xF;
        if (td > 3 || ta > 3) {
          // Td/Ta are 2-bit per T.81 B.2.3; huff_dc/huff_ac are 4 entries, so
          // an unvalidated nibble from a corrupt SOS indexed out of bounds
          // (heap OOB read, crash depending on heap layout — found by the
          // fuzz corpus under ASan)
          rc = PTPU_JPEG_CORRUPT;
          goto done;
        }
        comps[found].dc_tbl = td;
        comps[found].ac_tbl = ta;
        scan_comps[i] = found;
      }
      int Ss = seg[1 + 2 * ns];
      int Se = seg[2 + 2 * ns];
      int Ah = seg[3 + 2 * ns] >> 4;
      int Al = seg[3 + 2 * ns] & 0xF;

      if (!allocated) {
        // first scan: verify the layout; progressive scans add to the coefficients,
        // so their buffers start at zero (a baseline scan writes every block whole)
        for (int c = 0; c < ncomp; c++) {
          if (!qt_present[comps[c].tq]) {
            rc = PTPU_JPEG_CORRUPT;
            goto done;
          }
        }
        if (height != expect->height || width != expect->width ||
            ncomp != expect->ncomp) {
          rc = PTPU_JPEG_LAYOUT_MISMATCH;
          goto done;
        }
        for (int c = 0; c < ncomp; c++) {
          int bx = mcus_x * comps[c].h;
          int by = mcus_y * comps[c].v;
          if (comps[c].h != expect->h_samp[c] || comps[c].v != expect->v_samp[c] ||
              by != expect->blocks_y[c] || bx != expect->blocks_x[c]) {
            rc = PTPU_JPEG_LAYOUT_MISMATCH;
            goto done;
          }
          if (progressive) memset(dst[c], 0, (size_t)by * bx * 64 * sizeof(int16_t));
          const int32_t* zz = qt_zz[comps[c].tq];
          int32_t* qout = qdst + (size_t)c * 64;
          for (int k = 0; k < 64; k++) qout[kZigzagToNatural[k]] = (uint16_t)zz[k];
        }
        allocated = true;
      }

      if (progressive) {
        // table presence: DC-first scans need DC tables; AC scans need the AC table;
        // DC refinement (Ah>0, Ss==0) is raw bits, no table
        for (int i = 0; i < ns; i++) {
          const JComp& sc = comps[scan_comps[i]];
          if (Ss == 0 && Ah == 0 && !huff_dc[sc.dc_tbl].present) {
            rc = PTPU_JPEG_CORRUPT;
            goto done;
          }
          if (Ss > 0 && !huff_ac[sc.ac_tbl].present) {
            rc = PTPU_JPEG_CORRUPT;
            goto done;
          }
        }
        ProgScanArgs pargs;
        pargs.data = data;
        pargs.len = len;
        pargs.start = pos + seglen;
        pargs.comps = comps;
        pargs.scan_comps = scan_comps;
        pargs.ns = ns;
        pargs.Ss = Ss;
        pargs.Se = Se;
        pargs.Ah = Ah;
        pargs.Al = Al;
        pargs.huff_dc = huff_dc;
        pargs.huff_ac = huff_ac;
        pargs.restart_interval = restart_interval;
        pargs.width = width;
        pargs.height = height;
        pargs.hmax = hmax;
        pargs.vmax = vmax;
        pargs.mcus_x = mcus_x;
        pargs.mcus_y = mcus_y;
        pargs.blocks = dst;
        pargs.out_bx = expect->blocks_x;
        int64_t next_pos = 0;
        rc = decode_progressive_scan(pargs, &next_pos);
        if (rc != PTPU_JPEG_OK) goto done;
        scans_done++;
        rc = PTPU_JPEG_NO_SCAN;  // re-armed; success is decided at EOI
        pos = next_pos;
        continue;  // keep parsing markers: DHT/DRI/SOS/EOI follow
      }

      // ---- baseline: one interleaved scan covering every component ----
      if (ns != ncomp) {
        // non-interleaved multi-scan baseline: rare; the codec's host_stage_decode
        // catches the resulting ValueError and falls back to full cv2 host decode
        rc = PTPU_JPEG_UNSUPPORTED_MODE;
        goto done;
      }
      for (int c = 0; c < ncomp; c++) {
        if (!huff_dc[comps[c].dc_tbl].present || !huff_ac[comps[c].ac_tbl].present) {
          rc = PTPU_JPEG_CORRUPT;
          goto done;
        }
      }

      rc = decode_baseline_scan(BitSrc{data, len, pos + seglen, false}, comps, ncomp,
                                huff_dc, huff_ac, restart_interval, mcus_x, mcus_y,
                                dst, expect->blocks_x, st);
      goto done;
    }
    pos += seglen;
  }
  // progressive streams succeed at EOI (or end of data) once any scan landed; their
  // profile is read off the finished coefficients (a refinement may change a value
  // after its first scan)
  if (progressive && allocated && scans_done > 0) {
    rc = PTPU_JPEG_OK;
    for (int c = 0; c < ncomp; c++)
      fold_specmax(dst[c], (int64_t)expect->blocks_y[c] * expect->blocks_x[c],
                   st->specmax[c]);
  }

done:
  return rc;
}

// Parse only as far as the frame header; fills the decode layout without touching the
// entropy-coded scan. Used by the batch API to size the stacked buffers.
int ptpu_jpeg_parse_layout(const uint8_t* data, int64_t len, PtpuJpegLayout* out) {
  memset(out, 0, sizeof(*out));
  if (len < 4 || data[0] != 0xFF || data[1] != 0xD8) return PTPU_JPEG_NOT_JPEG;
  int64_t pos = 2;
  while (pos < len) {
    if (data[pos] != 0xFF) {
      pos++;
      continue;
    }
    if (pos + 1 >= len) break;
    uint8_t marker = data[pos + 1];
    pos += 2;
    if (marker == 0xD8 || marker == 0x01 || (marker >= 0xD0 && marker <= 0xD7)) continue;
    if (marker == 0xD9) break;
    if (pos + 2 > len) return PTPU_JPEG_CORRUPT;
    int seglen = be16(data + pos);
    if (seglen < 2 || pos + seglen > len) return PTPU_JPEG_CORRUPT;
    const uint8_t* seg = data + pos + 2;
    int segbytes = seglen - 2;
    if (marker == 0xC0 || marker == 0xC1 || marker == 0xC2) {  // baseline + progressive
      if (segbytes < 6) return PTPU_JPEG_CORRUPT;
      if (seg[0] != 8) return PTPU_JPEG_NOT_8BIT;
      out->height = be16(seg + 1);
      out->width = be16(seg + 3);
      out->ncomp = seg[5];
      if (out->ncomp < 1 || out->ncomp > 4 || segbytes < 6 + 3 * out->ncomp)
        return PTPU_JPEG_BAD_COMPONENTS;
      int hmax = 1, vmax = 1;
      for (int i = 0; i < out->ncomp; i++) {
        out->h_samp[i] = seg[7 + 3 * i] >> 4;
        out->v_samp[i] = seg[7 + 3 * i] & 0xF;
        if (out->h_samp[i] < 1 || out->h_samp[i] > 4 || out->v_samp[i] < 1 ||
            out->v_samp[i] > 4)
          return PTPU_JPEG_BAD_COMPONENTS;
        if (out->h_samp[i] > hmax) hmax = out->h_samp[i];
        if (out->v_samp[i] > vmax) vmax = out->v_samp[i];
      }
      int mcus_x = (out->width + 8 * hmax - 1) / (8 * hmax);
      int mcus_y = (out->height + 8 * vmax - 1) / (8 * vmax);
      for (int i = 0; i < out->ncomp; i++) {
        out->blocks_x[i] = mcus_x * out->h_samp[i];
        out->blocks_y[i] = mcus_y * out->v_samp[i];
      }
      return PTPU_JPEG_OK;
    }
    if (marker == 0xC3 || marker == 0xC5 || marker == 0xC6 ||
        marker == 0xC7 || marker == 0xC9 || marker == 0xCA || marker == 0xCB ||
        marker == 0xCD || marker == 0xCE || marker == 0xCF)
      return PTPU_JPEG_UNSUPPORTED_MODE;
    pos += seglen;
  }
  return PTPU_JPEG_NO_SCAN;
}

// Batched decode: n streams (stream i = datas[i][0..lens[i])), all expected to share
// ``expect``'s layout, written into caller-allocated stacked buffers:
//   out_blocks[c] : (n, blocks_y[c]*blocks_x[c], 64) int16, C-contiguous
//   out_qtabs     : (n, ncomp, 64) int32, natural order
//   out_kmax      : per component (size 4), the highest ZIGZAG position any stream
//                   holds a nonzero coefficient at — the caller may ship only the
//                   prefix up to it (ptpu_jpeg_zigzag_truncate)
//   out_specmax   : (ncomp, 64) int32, per zigzag position the largest |coefficient|
//                   of any stream (what ptpu_jpeg_specmax gives over out_blocks)
//   out_symbols   : (2,) int64, the Huffman symbols decoded and those of them that
//                   one lookup resolved, magnitude bits included
// status[i] = PTPU_JPEG_OK or the stream's error code (its slice is left zeroed and
// left out of kmax and specmax; the caller re-decodes failed rows individually).
// Returns the number of failed streams. One call decodes a whole row group, or one
// image (n = 1), with the GIL released.
int ptpu_jpeg_decode_batch(const uint8_t* const* datas, const int64_t* lens, int32_t n,
                           const PtpuJpegLayout* expect, int16_t* const* out_blocks,
                           int32_t* out_qtabs, int32_t* out_kmax, int32_t* out_specmax,
                           int64_t* out_symbols, int32_t* status) {
  const int ncomp = expect->ncomp < 4 ? expect->ncomp : 4;
  size_t stride[4];
  for (int c = 0; c < ncomp; c++)
    stride[c] = (size_t)expect->blocks_y[c] * expect->blocks_x[c] * 64;
  memset(out_specmax, 0, (size_t)ncomp * 64 * sizeof(int32_t));
  out_symbols[0] = out_symbols[1] = 0;
  int failures = 0;
  StreamStats st;
  for (int32_t i = 0; i < n; i++) {
    int16_t* dst[4] = {nullptr, nullptr, nullptr, nullptr};
    for (int c = 0; c < ncomp; c++) dst[c] = out_blocks[c] + (size_t)i * stride[c];
    int rc = decode_impl(datas[i], lens[i], expect, dst,
                         out_qtabs + (size_t)i * expect->ncomp * 64, &st);
    status[i] = rc;
    out_symbols[0] += st.symbols;
    out_symbols[1] += st.fast_symbols;
    if (rc != PTPU_JPEG_OK) {
      failures++;
      for (int c = 0; c < ncomp; c++) memset(dst[c], 0, stride[c] * sizeof(int16_t));
    } else {
      for (int c = 0; c < ncomp; c++)
        for (int j = 0; j < 64; j++)
          out_specmax[c * 64 + j] = std::max(out_specmax[c * 64 + j], st.specmax[c][j]);
    }
  }
  for (int c = 0; c < 4; c++) {
    out_kmax[c] = 0;
    for (int j = 63; c < ncomp && j > 0; j--)
      if (out_specmax[c * 64 + j]) {
        out_kmax[c] = j;
        break;
      }
  }
  return failures;
}

// Pack the zigzag prefix: src (nblocks, 64) int16 natural order → dst (nblocks, k)
// int16 where dst[b, j] = src[b, zigzag_to_natural(j)]. Coefficients beyond zigzag
// index k-1 are dropped (the caller guarantees they are zero via out_kmax). Reads only
// the needed elements — ~k/64 of the bytes a numpy fancy-gather touches.
void ptpu_jpeg_zigzag_truncate(const int16_t* src, int16_t* dst, int64_t nblocks,
                               int32_t k) {
  copy_blocks<true>(src, 64, nblocks, k, dst);
}

// 12-bit coefficient pack: src (nvals,) int16 → dst (nvals * 3 / 2,) uint8, two
// values per 3 bytes, little-endian nibble layout:
//   dst[0] = v0 & 0xFF;  dst[1] = ((v0 >> 8) & 0xF) | ((v1 & 0xF) << 4);
//   dst[2] = (v1 >> 4) & 0xFF
// (values stored as 12-bit two's complement). Returns 0 on success, -1 when any
// |value| exceeds the 12-bit range (caller ships int16 instead; dst contents are
// then unspecified). nvals must be even — the caller packs whole (block, k) rows
// with even k. Quantized DCT coefficients exceed ±2047 only at extreme qualities
// (quant step 1–2 with saturated content), so the fallback is rare but mandatory.
int32_t ptpu_jpeg_pack12(const int16_t* src, uint8_t* dst, int64_t nvals) {
  for (int64_t i = 0; i < nvals; i += 2)
    if (!put12(dst + (i / 2) * 3, src[i], src[i + 1])) return -1;
  return 0;
}

// Per-zigzag-position max |coefficient| over a stack of blocks: out[j] = max_b
// |block_b[zigzag position j]| for j in [0, k). ``is_zigzag`` says whether block rows
// are already zigzag-prefix packs of width k (ptpu_jpeg_zigzag_truncate output) or
// natural-order 64-wide rows (k must then be 64). The spectral range profile drives
// the per-position bit-width transfer split (ptpu_jpeg_pack_split): high zigzag
// positions carry heavily-quantized values that fit 8 or 4 bits even on sharp
// photographic content that defeats zigzag truncation outright.
void ptpu_jpeg_specmax(const int16_t* src, int64_t nblocks, int32_t k,
                       int32_t is_zigzag, int32_t* out) {
  for (int32_t j = 0; j < k; j++) out[j] = 0;
  for (int64_t b = 0; b < nblocks; b++) {
    const int16_t* s = src + b * k;
    for (int32_t j = 0; j < k; j++) {
      int32_t v = is_zigzag ? s[j] : s[kZigzagToNatural[j]];
      if (v < 0) v = -v;
      if (v > out[j]) out[j] = v;
    }
  }
}

// Spectral-split coefficient pack, one pass: block row (zigzag order, width k) ->
// three slabs with per-position bit widths chosen by the caller from the specmax
// profile:
//   head: zigzag positions [0, k1)  -> 12-bit pairs (ptpu_jpeg_pack12 layout), k1 even
//   mid : positions [k1, k2)        -> int8
//   tail: positions [k2, k)         -> 4-bit two's-complement nibble pairs
//         (low nibble = even position), k - k2 even
// ``is_zigzag`` as in ptpu_jpeg_specmax. Returns 0 on success; -1/-2/-3 when a value
// exceeds its tier's range (head/mid/tail respectively — caller falls back to a wider
// pack; dst contents are then unspecified). Exact by construction: the unpacked
// values are bit-identical to src.
int32_t ptpu_jpeg_pack_split(const int16_t* src, int64_t nblocks, int32_t k,
                             int32_t is_zigzag, int32_t k1, int32_t k2,
                             uint8_t* head, int8_t* mid, uint8_t* tail) {
  return is_zigzag ? split_blocks<false>(src, k, nblocks, k, k1, k2, head, mid, tail)
                   : split_blocks<true>(src, k, nblocks, k, k1, k2, head, mid, tail);
}

// Stage-2's shipped input, every component in one pass straight from each row's own
// coefficients: for component c, rows[c * n + i] points at row i's (nblocks[c], 64)
// int16 natural-order blocks (a row of its row group's stacked buffer, or a lone
// row's blocks), and row i of its output is written at row i. spec[4c..4c+3] is
// (k, mode, k1, k2), and outs[3c..3c+2] its slabs; ``mode`` names the variant:
//   -1 skip  : the component is left alone
//    0 int16 : out0 (n, nblocks, k) int16, the zigzag prefix (ptpu_jpeg_zigzag_truncate)
//              or, at k == 64, the natural-order row as it is
//    1 pack12: out0 (n, nblocks, k*3/2) uint8, the same values as 12-bit pairs
//    2 split : out0/out1/out2 the head/mid/tail slabs of ptpu_jpeg_pack_split over
//              zigzag positions [0, k), split at (k1, k2)
// The rows are cut into ``nthreads`` contiguous slices, one a thread (the calling
// thread takes the first), each packing every component of its rows. status[c] is 0,
// or the range failure code (pack12's -1, the split's -1/-2/-3) a row of component c
// met: its output is then unspecified, and no slice packs it further. Returns the
// number of components that failed.
int32_t ptpu_jpeg_pack_rows(const int16_t* const* rows, int64_t n, int32_t ncomp,
                            const int64_t* nblocks, const int32_t* spec,
                            void* const* outs, int32_t* status, int32_t nthreads) {
  if (ncomp < 1 || ncomp > 4) return -1;
  PackComp comps[4];
  std::atomic<int32_t> failed[4];
  for (int32_t c = 0; c < ncomp; c++) {
    const int32_t* sp = spec + 4 * c;
    comps[c] = PackComp{rows + c * n, nblocks[c], sp[0], sp[1], sp[2], sp[3],
                        outs[3 * c], (int8_t*)outs[3 * c + 1], (uint8_t*)outs[3 * c + 2]};
    failed[c].store(sp[1] < 0 ? 1 : 0);  // a skipped component reads as done
  }
  auto slice = [&comps, &failed, ncomp](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; i++) {
      bool any = false;
      for (int32_t c = 0; c < ncomp; c++) {
        if (failed[c].load(std::memory_order_relaxed) != 0) continue;
        any = true;
        int32_t rc = pack_row(comps[c], i);
        if (rc != 0) {
          int32_t none = 0;
          failed[c].compare_exchange_strong(none, rc);
        }
      }
      if (!any) return;
    }
  };
  if (nthreads > n) nthreads = (int32_t)n;
  if (nthreads < 1) nthreads = 1;
  std::vector<std::thread> workers;
  for (int32_t t = 1; t < nthreads; t++) {
    int64_t lo = n * t / nthreads, hi = n * (t + 1) / nthreads;
    try {
      workers.emplace_back(slice, lo, hi);
    } catch (const std::system_error&) {
      slice(lo, hi);  // no thread to be had: this one does the slice
    }
  }
  slice(0, n / nthreads);
  for (auto& w : workers) w.join();
  int32_t failures = 0;
  for (int32_t c = 0; c < ncomp; c++) {
    status[c] = comps[c].mode < 0 ? 0 : failed[c].load();
    failures += status[c] != 0;
  }
  return failures;
}

}  // extern "C"
